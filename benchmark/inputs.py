"""Everything a run is fed, made from ``--seed`` by the benchmark itself.

Rows, the monitor's fitted arrays and the weights are the benchmark's, not
the program's: the program and the plain reference are handed the same
arrays, and neither makes anything the other consumes. The same seed gives
the same inputs.

- rows: already-encoded credit records (categorical ids within the schema's
  cardinalities, standardised numerics), correlated, with a heavy tail so
  that the outlier detector has something to flag and a small shift against
  the monitor's reference so that the drift scores are not all 0 or 1;
- monitor arrays: fitted on a reference sample of the unshifted population
  with plain NumPy (category counts, a sorted reference sample per numeric
  feature, Mahalanobis mean / precision / threshold);
- weights: one jitted call on the device fills every leaf of the program's
  parameter tree (shapes from ``jax.eval_shape``, nothing initialised by the
  program), float32 as the program keeps them. Biases and LayerNorm
  parameters are random too, so that a comparison covers them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def split_seed(seed: int) -> tuple[int, int]:
    """``--seed`` may exceed 2**31: the low 31 bits and what is above."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return seed & 0x7FFFFFFF, seed >> 31


@dataclass
class Population:
    """The seeded distribution rows are drawn from."""

    cat_cdfs: list[np.ndarray]  # per categorical feature, cumulative probs
    mix: np.ndarray  # f64 [M, M] latent -> correlated numerics, unit variance
    tail_share: float
    tail_scale: float
    shift: np.ndarray  # f64 [M] per-feature offset of a shifted draw
    flat: float  # how far a shifted draw's category probs move to uniform


def make_population(seed: int, schema: dict, data: dict) -> Population:
    rng = np.random.default_rng([*split_seed(seed), 11])
    probs = [rng.dirichlet(np.full(card, 2.0)) for card in schema["cards"]]
    m = schema["num_numeric"]
    mix = np.eye(m) + float(data.get("correlation", 0.4)) * rng.standard_normal(
        (m, m)
    ) / math.sqrt(m)
    mix /= np.sqrt((mix**2).sum(axis=0, keepdims=True))
    drift = float(data.get("drift_shift", 0.0))
    return Population(
        cat_cdfs=[np.cumsum(p) for p in probs],
        mix=mix,
        tail_share=float(data.get("tail_share", 0.03)),
        tail_scale=float(data.get("tail_scale", 2.5)),
        shift=drift * rng.uniform(-1.0, 1.0, m),
        flat=drift,
    )


def draw_rows(
    pop: Population, rng: np.random.Generator, n: int, shifted: bool
) -> tuple[np.ndarray, np.ndarray]:
    """``n`` encoded rows: (int32 [n, C], float32 [n, M])."""
    cat = np.empty((n, len(pop.cat_cdfs)), np.int32)
    for j, cdf in enumerate(pop.cat_cdfs):
        if shifted and pop.flat:
            p = np.diff(cdf, prepend=0.0)
            p = (1.0 - pop.flat) * p + pop.flat / p.size
            cdf = np.cumsum(p)
        ids = np.searchsorted(cdf, rng.random(n), side="right")
        cat[:, j] = np.minimum(ids, cdf.size - 1)
    num = rng.standard_normal((n, pop.mix.shape[0])) @ pop.mix
    tail = rng.random(n) < pop.tail_share
    num[tail] *= pop.tail_scale
    if shifted:
        num += pop.shift
    return cat, num.astype(np.float32)


def make_file(seed: int, schema: dict, data: dict, rows: int):
    """One scored file. Up to ``base_rows`` rows are all distinct draws; a
    longer file repeats a base of that many under a seeded index, since
    drawing millions of fresh rows would be most of a run's set-up (no cell
    needs it today; PERF.md's one 1,000,000-row job was made so)."""
    pop = make_population(seed, schema, data)
    rng = np.random.default_rng([*split_seed(seed), 12])
    base_rows = min(rows, int(data.get("base_rows", rows)))
    cat, num = draw_rows(pop, rng, base_rows, shifted=True)
    if base_rows < rows:
        idx = rng.integers(0, base_rows, rows)
        idx[:base_rows] = rng.permutation(base_rows)  # every base row is there
        cat, num = cat[idx], num[idx]
    return cat, num


def fit_monitor_arrays(seed: int, schema: dict, data: dict, assumed: dict) -> dict:
    """The monitor's fitted state as plain float32 arrays, from a reference
    sample of the unshifted population."""
    pop = make_population(seed, schema, data)
    rng = np.random.default_rng([*split_seed(seed), 13])
    n = int(data.get("monitor_fit_rows", 20000))
    cat, num = draw_rows(pop, rng, n, shifted=False)
    cards = schema["cards"]
    counts = np.zeros((len(cards), max(cards)), np.float32)
    for j, card in enumerate(cards):
        counts[j, :card] = np.bincount(cat[:, j], minlength=card)
    ref_size = min(int(assumed["monitor_drift_ref_size"]), n)
    ref = np.sort(num[rng.choice(n, ref_size, replace=False)], axis=0).T
    x = num.astype(np.float64)
    mean = x.mean(axis=0)
    cov = np.cov(x, rowvar=False) + 1e-6 * np.eye(x.shape[1])
    precision = np.linalg.inv(cov)
    centered = x - mean
    dist = np.einsum("ni,ij,nj->n", centered, precision, centered)
    return {
        "cat_ref_counts": counts,
        "num_ref_sorted": np.ascontiguousarray(ref, np.float32),
        "out_mean": mean.astype(np.float32),
        "out_precision": precision.astype(np.float32),
        "out_threshold": np.float32(
            np.quantile(dist, float(assumed["monitor_outlier_quantile"]))
        ),
    }


def _fan_in(name: str, shape: tuple[int, ...]) -> int:
    if name == "qkv":  # [..., in, 3, heads, head_dim]
        return shape[-4]
    if name == "out" and len(shape) >= 3:  # [..., heads, head_dim, out]
        return shape[-3] * shape[-2]
    return shape[-2]  # [..., in, out]


def make_weights(abstract_tree, seed: int):
    """Fill the parameter tree on the device in ONE jitted call."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    plan = []
    for path, leaf in leaves:
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        kind, owner = names[-1], names[-2] if len(names) > 1 else ""
        if kind == "kernel":
            rule = (1.0 / math.sqrt(_fan_in(owner, leaf.shape)), 0.0)
        elif kind in ("embedding", "pos_embed"):
            rule = (1.0 / math.sqrt(leaf.shape[-1]), 0.0)
        elif kind == "scale":
            rule = (0.1, 1.0)  # (std, mean)
        elif kind == "bias":
            rule = (0.1, 0.0)
        else:
            raise ValueError(f"no rule to fill parameter {names}")
        plan.append((leaf.shape, leaf.dtype, rule))

    low, high = split_seed(seed)
    sizes = [math.prod(shape) for shape, _, _ in plan]

    @jax.jit
    def fill(seed_words):
        # The seed is an ARGUMENT: as a Python number it would be a constant
        # of the program, every seed a new program, and the generator takes
        # some 15 s to compile on the chip (0.1 s to load from the cache).
        # ONE draw for the whole tree, cut into leaves, keeps the program small.
        key = jax.random.fold_in(jax.random.PRNGKey(seed_words[0]), seed_words[1])
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, offset = [], 0
        for size, (shape, dtype, (std, mean)) in zip(sizes, plan):
            piece = jax.lax.dynamic_slice_in_dim(flat, offset, size).reshape(shape)
            out.append((mean + std * piece).astype(dtype))
            offset += size
        return out

    words = jnp.asarray([low, high], jnp.uint32)
    return jax.tree_util.tree_unflatten(treedef, fill(words))
