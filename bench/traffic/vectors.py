"""Seeded corpora and query streams, made on the device.

A copy of `repro.data.vectors` (`make_clustered_vectors`,
`SkewedVectorDataset`) rewritten in `jax.random`, so that a run makes its
data on the chip from `--seed` instead of in host numpy:

  * Zipf-distributed cluster sizes: generator centre i draws a share of
    the rows proportional to a fixed Zipf weight, the weights permuted by
    the corpus seed;
  * Zipf query popularity over the same centres, permuted likewise;
  * co-occurring residual patterns: every row is its centre plus one of
    `pattern_pool` shared patterns plus small noise, so PQ codes of
    co-located rows repeat and the co-occurrence mining has structure.

Values are emitted in the source's value type: the float model is scaled,
offset, rounded and clipped to `uint8` (BIGANN) or `int8` (MSSPACEV).
The corpus comes in fixed-size chunks of one jitted program, so the
device never holds more than one chunk's temporaries.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

VALUE_RANGES = {"uint8": (0, 255), "int8": (-128, 127)}
CHUNK_ROWS = 1 << 18


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one stream of one seed; any whole seed, also past
    32 bits, maps to distinct keys."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    key = jax.random.PRNGKey(int(words[0]))
    return jax.random.fold_in(key, int(words[1]))


@dataclasses.dataclass(frozen=True)
class VectorModel:
    """The generator's parameters (a configuration's `data` block)."""

    dim: int
    n_centers: int
    value_type: str
    value_scale: float
    value_offset: float
    size_zipf: float = 1.3
    popularity_zipf: float = 1.1
    pattern_pool: int = 64
    center_scale: float = 5.0
    noise: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> "VectorModel":
        d = cfg["data"]
        return cls(dim=cfg["dim"], n_centers=d["n_centers"],
                   value_type=d["value_type"],
                   value_scale=d["value_scale"],
                   value_offset=d["value_offset"],
                   size_zipf=d["size_zipf"],
                   popularity_zipf=d["popularity_zipf"],
                   pattern_pool=d["pattern_pool"],
                   center_scale=d["center_scale"], noise=d["noise"])


def _zipf_cdf(key, n: int, s: float) -> jax.Array:
    w = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32) ** s
    w = jax.random.permutation(key, w)
    cdf = jnp.cumsum(w / jnp.sum(w))
    return cdf.at[-1].set(1.0)


@functools.partial(jax.jit, static_argnames=("model",))
def _world(key, model: VectorModel):
    """Centres, pattern pool and the two Zipf CDFs of one seed."""
    k_c, k_s, k_p, k_pool = jax.random.split(key, 4)
    centers = model.center_scale * jax.random.normal(
        k_c, (model.n_centers, model.dim), jnp.float32)
    pool = model.noise * jax.random.normal(
        k_pool, (model.pattern_pool, model.dim), jnp.float32)
    return (centers, pool, _zipf_cdf(k_s, model.n_centers, model.size_zipf),
            _zipf_cdf(k_p, model.n_centers, model.popularity_zipf))


def _to_values(x, model: VectorModel):
    lo, hi = VALUE_RANGES[model.value_type]
    v = jnp.round(x * model.value_scale + model.value_offset)
    return jnp.clip(v, lo, hi).astype(model.value_type)


@functools.partial(jax.jit, static_argnames=("model", "n"))
def _rows(key, chunk, centers, pool, size_cdf, model: VectorModel, n: int):
    k_a, k_p, k_n = jax.random.split(jax.random.fold_in(key, chunk), 3)
    assign = jnp.searchsorted(size_cdf, jax.random.uniform(k_a, (n,)))
    pattern = jax.random.randint(k_p, (n,), 0, model.pattern_pool)
    noise = 0.1 * model.noise * jax.random.normal(k_n, (n, model.dim))
    return _to_values(centers[assign] + pool[pattern] + noise, model)


@functools.partial(jax.jit, static_argnames=("model", "n"))
def _queries(key, centers, pop_cdf, model: VectorModel, n: int):
    k_w, k_n = jax.random.split(key)
    which = jnp.searchsorted(pop_cdf, jax.random.uniform(k_w, (n,)))
    noise = model.noise * jax.random.normal(k_n, (n, model.dim))
    return _to_values(centers[which] + noise, model)


class VectorSource:
    """One seed's corpus and query streams.  Queries may come from
    another seed over the same centres: a deployment's corpus is fixed
    while its traffic varies."""

    def __init__(self, model: VectorModel, seed: int):
        self.model = model
        self.seed = int(seed)
        self._world = _world(seed_key(seed, 0), model)

    def corpus(self, n: int) -> np.ndarray:
        """(n, dim) host array of the corpus in the source's value type,
        made chunk by chunk on the device."""
        centers, pool, size_cdf, _ = self._world
        key = seed_key(self.seed, 1)
        out = np.empty((n, self.model.dim), self.model.value_type)
        for c, s in enumerate(range(0, n, CHUNK_ROWS)):
            rows = _rows(key, c, centers, pool, size_cdf, self.model,
                         CHUNK_ROWS)
            out[s:s + CHUNK_ROWS] = np.asarray(rows)[:n - s]
        return out

    def queries(self, n: int, stream: int,
                seed: int | None = None) -> np.ndarray:
        """(n, dim) host array of popularity-skewed queries of one stream
        (stream 0 and up; streams are independent), drawn from `seed`
        (the corpus seed where None)."""
        centers, _, _, pop_cdf = self._world
        key = seed_key(self.seed if seed is None else seed, 2 + stream)
        return np.asarray(_queries(key, centers, pop_cdf, self.model, n))
