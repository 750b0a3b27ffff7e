"""The user axis at scale: block decomposition + segment-reduced
aggregation.

The paper evaluates up to 15 concurrent users per balancer; the ROADMAP
north star is millions. The engine's config axis already fuses thousands
of configurations into one device program, so the scaled user axis rides
it: a configuration with ``n_users = N`` and ``user_block = C`` is
decomposed into ``K = ceil(N / C)`` **user blocks** — independent
balancer replicas, each serving its contiguous slice of ≤ C users with
its own queue/estimator/dispatch state. Block rows are ordinary config
rows, so the whole fleet of replicas vmaps, shards over a mesh
(``shard_map`` splits blocks across devices — per-user queue and
workload state is literally sharded alongside configs) and fleet-stacks
with zero new engine machinery. Per-config metrics come back by
**segment reduction** over each config's contiguous block rows.

Reduction contract (pinned by ``tests/test_useraxis.py``): every
reduction here is a *left fold in index order*. ``jax.ops.segment_sum``
scatter-adds elements sequentially, which makes it bit-stable across
eager/jit and across the padded-dense and ragged-flat layouts of the
same values. A plain ``where(mask, x, 0).sum(-1)`` is NOT that — XLA
vectorizes row reductions with reassociation and drifts by float ULPs —
so the dense masked reduction (:func:`masked_user_sum`) is implemented
via the same segment fold (pad entries map to a dropped segment) rather
than ``jnp.sum``. That is what makes the segment-reduced aggregation
bit-equal to the dense masked reference, including all-padded and
single-user edge cases, and what keeps ``K = 1`` configs bit-identical
through the aggregation pass (a one-element fold, a divide by 1.0 and a
one-element max are all exact).

Aggregation semantics over a config's blocks
(:func:`aggregate_block_summaries`): blocks are balancer replicas
running *concurrently*, each over the same scan length, so

  * per-request means (latency, energy, mAP, estimator accuracy) are
    request-weighted means = uniform means over blocks (every block
    contributes the same number of post-warmup requests);
  * ``throughput_rps`` sums over blocks (independent replicas serve in
    parallel);
  * ``makespan_s`` is the max over blocks (the slowest replica);
  * ``latency_p90_ms`` is the **exact fleet-wide percentile of the merged
    latency histogram**: each block row emits a fixed-bin log-spaced
    histogram (:func:`latency_histogram`; counts are integer-valued
    float32, exact under addition up to 2^24 per bin), the block
    histograms segment-sum into the config's pooled histogram inside
    the device program (on a mesh each device merges the rows it holds
    and a ``psum`` adds the partials, so the histogram arrives per
    config and no per-block histogram leaves a device; see
    ``repro.core.simulator._fused_summaries``), and
    :func:`histogram_p90` interpolates the percentile from the pooled
    counts. Because histogram merging is exact, the K-block aggregate is
    bit-identical to running the same estimator on the pooled dense
    latency set — partition-invariant by construction, and the same
    bits whichever device holds which block — with quantization bounded
    by the bin resolution (~0.5% relative at 4096 log bins over
    [1e-5, 1e4] s). Single-block configs keep the exact
    ``jnp.percentile`` passthrough (the golden fixtures pin it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DEFAULT_STREAM_CHUNK", "HIST_BINS", "HIST_LO_S", "HIST_HI_S",
           "n_user_blocks", "block_sizes", "block_segments",
           "segment_user_sum", "segment_user_mean", "segment_user_max",
           "masked_user_sum", "masked_user_mean", "latency_histogram",
           "histogram_p90", "aggregate_block_summaries", "grid_nbytes"]

f32 = jnp.float32
i32 = jnp.int32

#: Default per-device-call chunk width for streamed workload draws
#: (``WorkloadSource.stream_draws``): bounds the largest single draw
#: program at ~256 KiB of int32 per leaf regardless of ``n_users``.
DEFAULT_STREAM_CHUNK = 65536


# ------------------------------------------------- block decomposition --

def n_user_blocks(n_users: int, user_block: int) -> int:
    """How many balancer-replica blocks a config of ``n_users`` splits
    into at block size ``user_block`` (at least 1)."""
    if user_block <= 0:
        raise ValueError(f"user_block must be positive, got {user_block}")
    return max(1, math.ceil(n_users / user_block))


def block_sizes(n_users: int, user_block: int) -> list[int]:
    """Users per block: ``user_block`` for every full block, the
    remainder on the last (``[N]`` when ``N <= user_block``)."""
    k = n_user_blocks(n_users, user_block)
    return [min(user_block, n_users - b * user_block) for b in range(k)]


def block_segments(blocks_per_cfg) -> np.ndarray:
    """Config-id segment vector for an expanded grid: config ``i``'s
    ``blocks_per_cfg[i]`` block rows are contiguous, so the segment ids
    are ``[0]*K0 + [1]*K1 + ...`` (int32)."""
    return np.repeat(np.arange(len(blocks_per_cfg), dtype=np.int32),
                     np.asarray(blocks_per_cfg, np.int64))


# ------------------------------------------- canonical left-fold sums --

def segment_user_sum(values, segments, num_segments: int):
    """Segment sum over the LEADING axis, accumulated as a left fold in
    index order (``jax.ops.segment_sum``'s scatter-add order) — the one
    canonical reduction every user-axis aggregation goes through."""
    return jax.ops.segment_sum(jnp.asarray(values),
                               jnp.asarray(segments, i32),
                               num_segments=num_segments)


def segment_user_mean(values, segments, num_segments: int):
    """Left-fold segment mean; empty segments give 0 (safe divide), a
    one-element segment passes its value through bitwise (``x / 1.0``)."""
    values = jnp.asarray(values)
    seg = jnp.asarray(segments, i32)
    total = segment_user_sum(values, seg, num_segments)
    count = segment_user_sum(jnp.ones(seg.shape, values.dtype), seg,
                             num_segments)
    shape = count.shape + (1,) * (total.ndim - count.ndim)
    count = count.reshape(shape)
    return total / jnp.maximum(count, jnp.ones((), values.dtype))


def segment_user_max(values, segments, num_segments: int):
    """Segment max over the leading axis; empty segments give 0 (not
    ``-inf`` — the aggregation consumers treat absent as zero work)."""
    out = jax.ops.segment_max(jnp.asarray(values),
                              jnp.asarray(segments, i32),
                              num_segments=num_segments)
    return jnp.where(jnp.isneginf(out), jnp.zeros((), out.dtype), out)


def masked_user_sum(values, n_users):
    """Dense masked per-user reduction: ``values`` is ``(B, U)`` padded,
    row ``b``'s live entries are ``values[b, :n_users[b]]``; returns the
    ``(B,)`` per-row sums.

    Implemented via the SAME left fold as :func:`segment_user_sum` — pad
    entries map to segment ``B``, which is dropped — so it is bit-equal
    to the segment reduction of the ragged flat layout (property-tested
    in ``tests/test_useraxis.py``). ``where(mask, v, 0).sum(-1)`` would
    NOT be: XLA reassociates vectorized row sums.
    """
    values = jnp.asarray(values)
    if values.ndim != 2:
        raise ValueError(f"masked_user_sum wants (B, U), got "
                         f"{values.shape}")
    b, u = values.shape
    live = jnp.arange(u) < jnp.asarray(n_users, i32)[:, None]
    seg = jnp.where(live, jnp.arange(b, dtype=i32)[:, None], b)
    return jax.ops.segment_sum(values.reshape(-1), seg.reshape(-1),
                               num_segments=b)


def masked_user_mean(values, n_users):
    """Dense masked per-user mean (all-padded rows give 0); bit-equal to
    :func:`segment_user_mean` on the ragged layout."""
    n = jnp.asarray(n_users, i32)
    total = masked_user_sum(values, n)
    count = n.astype(jnp.asarray(values).dtype)
    return total / jnp.maximum(count, jnp.ones((), count.dtype))


# ------------------------------------------ latency histogram merge -----

#: Fixed latency histogram geometry: log-spaced bins over
#: [``HIST_LO_S``, ``HIST_HI_S``] seconds. 4096 bins over 9 decades is
#: ~0.5% relative resolution — far below the seed-to-seed noise of any
#: percentile metric — while one histogram is a 16 KiB leaf.
HIST_BINS = 4096
HIST_LO_S = 1e-5
HIST_HI_S = 1e4

_LOG_LO = math.log(HIST_LO_S)
_LOG_SPAN = math.log(HIST_HI_S) - math.log(HIST_LO_S)


def _hist_edges():
    """The NB+1 bin edges in seconds (float64 host-side geometry)."""
    return np.exp(_LOG_LO + _LOG_SPAN * np.arange(HIST_BINS + 1)
                  / HIST_BINS)


def latency_histogram(latencies):
    """Fixed-bin log-histogram of a latency sample (seconds) -> ``(NB,)``
    float32 counts. Counts are integer-valued float32, so histograms add
    EXACTLY in any order and grouping (up to 2^24 requests per bin) —
    the property that makes the K-block percentile merge
    partition-invariant, and lets the sweep engine merge a config's
    block histograms on the devices that hold them. Out-of-range samples
    clamp into the edge bins."""
    lat = jnp.asarray(latencies, f32).reshape(-1)
    idx = jnp.floor((jnp.log(jnp.maximum(lat, HIST_LO_S)) - _LOG_LO)
                    / _LOG_SPAN * HIST_BINS).astype(i32)
    idx = jnp.clip(idx, 0, HIST_BINS - 1)
    return jax.ops.segment_sum(jnp.ones(lat.shape, f32), idx,
                               num_segments=HIST_BINS)


def histogram_p90(hist, q: float = 90.0):
    """Percentile (default p90) of a ``(..., NB)`` latency histogram, in
    seconds: fractional rank ``q/100 * (n - 1)`` (``jnp.percentile``'s
    'linear' convention), located by the count CDF and linearly
    interpolated inside its bin. A deterministic pure function of the
    counts — so ``histogram_p90(sum_k hist_k)`` is bit-identical to the
    single-shot histogram of the pooled sample."""
    h = jnp.asarray(hist, f32)
    edges = jnp.asarray(_hist_edges(), f32)
    cum = jnp.cumsum(h, axis=-1)
    n = cum[..., -1:]
    rank = q / 100.0 * jnp.maximum(n - 1.0, 0.0)
    k = jnp.argmax(cum > rank, axis=-1)
    cum_before = jnp.take_along_axis(cum, k[..., None], -1) \
        - jnp.take_along_axis(h, k[..., None], -1)
    in_bin = jnp.take_along_axis(h, k[..., None], -1)
    frac = (rank - cum_before + 0.5) / jnp.maximum(in_bin, 1.0)
    frac = jnp.clip(frac, 0.0, 1.0)
    left = edges[k][..., None]
    right = edges[k + 1][..., None]
    return (left + frac * (right - left))[..., 0]


# --------------------------------------------- block-row aggregation ----

#: Summary metrics that SUM over a config's blocks (independent balancer
#: replicas serving concurrently) instead of averaging.
_SUM_METRICS = frozenset({"throughput_rps"})
#: Summary metrics that take the MAX over blocks (slowest replica).
_MAX_METRICS = frozenset({"makespan_s"})


def aggregate_block_summaries(out: dict, segments, num_configs: int,
                              block_axis: int = -1) -> dict:
    """Fold per-block summary metrics back to per-config metrics.

    ``out`` maps metric name -> array whose ``block_axis`` (default:
    trailing, the engine's config axis) runs over the expanded block
    rows; ``segments`` maps each block row to its config. Means stay
    means (uniform over blocks — every block contributes equally many
    requests), throughput sums, makespan maxes; see the module docstring
    for the exact contract. A config with a single block passes through
    bit-identically.

    When ``out`` carries a ``latency_hist`` leaf — the config's merged
    histogram, laid out as the metric leaves with their block axis cut
    to ``num_configs`` and the bin axis trailing, as the sweep engine
    returns it after merging the block rows' histograms on device —
    ``latency_p90_ms`` is recomputed for multi-block configs as its
    exact percentile (:func:`histogram_p90`); single-block configs keep
    their ``jnp.percentile`` value bit-identically. The histogram leaf
    is consumed, not returned.
    """
    out = dict(out)
    hist = out.pop("latency_hist", None)
    seg = jnp.asarray(segments, i32)
    if int(seg.shape[0]) == num_configs:
        # K = 1 everywhere: the expanded grid IS the config grid
        return out

    def lead(v):
        return jnp.moveaxis(jnp.asarray(v), block_axis, 0)

    def unlead(v):
        return jnp.moveaxis(v, 0, block_axis)

    agg = {}
    for k, v in out.items():
        if k in _SUM_METRICS:
            agg[k] = unlead(segment_user_sum(lead(v), seg, num_configs))
        elif k in _MAX_METRICS:
            agg[k] = unlead(segment_user_max(lead(v), seg, num_configs))
        else:
            agg[k] = unlead(segment_user_mean(lead(v), seg, num_configs))
    if hist is not None:
        p90_ms = 1000.0 * histogram_p90(hist)
        bpc = segment_user_sum(jnp.ones((seg.shape[0],), f32), seg,
                               num_configs)
        agg["latency_p90_ms"] = jnp.where(bpc == 1.0,
                                          agg["latency_p90_ms"], p90_ms)
    return agg


# ------------------------------------------------- memory accounting ----

def grid_nbytes(grid) -> int:
    """Total bytes of a grid pytree's leaves — the array-size accounting
    the memory-ceiling tests assert on (RSS is too noisy to gate). The
    blocked layout keeps this at ``O(total_users)``: a 10^6-user config
    is ~8 MB of int32 leaves instead of an ``n_configs × n_users_max``
    dense pad."""
    return int(sum(np.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(grid)))
