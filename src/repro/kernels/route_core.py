"""THE shared block-routing core: one masked-greedy implementation for every
device router and its host oracle.

Three consumers share this module verbatim (DESIGN.md SS3.3 "One routing-
kernel substrate"):

  parallel/chunked_driver.py    — the chunk step (XLA scan): PKG, D- and
                                  W-Choices with online head tables
  parallel/sharded_router.py    — per-key candidate counts over 1..S shards
  kernels/moe_pkg_dispatch.py   — MoE expert dispatch (PKG-PoTC and the
                                  adaptive D-/W-Choices variants), where the
                                  "workers" are experts and each token block
                                  carries k slots of router-ranked candidates

plus every matching `ref_*` oracle in kernels/ref.py and the host router
modes in models/moe.py.  The kernel-side `route_block` speaks the TPU-native
formulation (one-hot-matmul load fetch + histogram update, no gathers); the
host-side `oracle_block_step` is the gather-based twin with identical mask /
sentinel / tie-break semantics.  Both share `_mask_and_flag` and
`head_table_ncand`, so the masks and the head-table lookup cannot drift.  The
W-sentinel water-fill has three formulations of one definition ("take the
global argmin, add one"), each checked against the others bit for bit in
tests/test_kernels.py.  The kernel side runs `waterfill_levels` without
capacities (loop-free: it counts picks per integer load level) and
`waterfill_assign` with them (sequential: one argmin per head lane); both
lower under Mosaic, so the XLA and the Pallas (MoE) callers share them.  The
oracle runs the loop-free `waterfill_picks` (top_k).

Vocabulary: `n_entities` is the number of routing targets — stream workers
for the routers, experts for MoE dispatch.  Loads are integer counts in f32
(IEEE-exact), the mask sentinel is 1e30 (greater than any reachable load),
and every argmin breaks ties to the lowest index.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.estimation import W_SENTINEL
from repro.core.hashing import splitmix32

__all__ = [
    "LANES",
    "MASK",
    "chunk_loads_spec",
    "chunk_rows_spec",
    "hash_candidates",
    "waterfill_picks",
    "waterfill_assign",
    "waterfill_levels",
    "pick_lane",
    "head_table_ncand",
    "route_block",
    "oracle_block_step",
]

# Mask sentinel: 1e30 is > any reachable load and fp32-exact; kernels and
# oracles both read it from here so they stay bit-identical.
MASK = 1e30

LANES = 128  # VPU lane width the global reduction pads to


def chunk_loads_spec(n_entities: int):
    """Out BlockSpec of a router's per-chunk loads, an (N/chunk, 1, n) array:
    grid step i writes row i as a (1, n) block.  The chunk axis is squeezed
    because a (1, n) block of an (N/chunk, n) array breaks the TPU rule that
    the second-minor block dim is a multiple of 8 or the full dim."""
    return pl.BlockSpec((pl.Squeezed(), 1, n_entities), lambda i: (i, 0, 0))


def chunk_rows_spec(chunk: int, block: int):
    """BlockSpec of a per-message array laid out (N/block, block): grid step
    i holds its chunk as chunk/block rows of one vector block each, and the
    kernel reads block b as row b.  A flat (chunk,) block would need every
    dynamic offset to be a multiple of 1024 on the TPU (its 1-D tiling)."""
    return pl.BlockSpec((chunk // block, block), lambda i: (i, 0))


def hash_candidates(kb, seeds, n_entities: int):
    """SplitMix32 candidate ids for a block of keys: (V,) x (d,) -> (V, d).

    The d seeds come from core.hashing.derive_seeds; the family is
    prefix-stable in d, so a d_max-wide candidate table masked down to its
    first 2 lanes reproduces plain PKG's candidates exactly.
    """
    h = splitmix32(kb.astype(jnp.uint32)[:, None] ^ seeds[None, :])  # (V, d)
    return (h % jnp.uint32(n_entities)).astype(jnp.int32)


def _padded_row(loads, inv_cap, n_workers):
    """(1, n_workers) loads / reciprocal-capacity rows padded to a LANES
    multiple: pad loads hold MASK (they never win an argmin), pad
    capacities 1.0.  Returns (row, icap or None, padded width)."""
    pad = -n_workers % LANES
    if not pad:
        return loads, inv_cap, n_workers
    row = jnp.concatenate([loads, jnp.full((1, pad), MASK, jnp.float32)], 1)
    if inv_cap is not None:
        inv_cap = jnp.concatenate(
            [inv_cap, jnp.ones((1, pad), jnp.float32)], 1
        )
    return row, inv_cap, n_workers + pad


def waterfill_picks(loads, *, n_workers, block, inv_cap=None):
    """First `block` picks of sequential global-argmin routing from the
    (1, n_workers) loads row: pick r is where the r-th head message of a
    block goes, with every earlier pick's unit load accounted.

    Pick 0 is the masked global argmin — worker lanes padded to a LANES
    multiple with the MASK sentinel (pad lanes can never win the min),
    ties broken to the lowest worker index, exactly w_choices_partition's
    `jnp.argmin(loads)` step.  The full sequence needs no sequential loop:
    worker j's t-th pick happens at running load L_j + t, and "repeatedly
    take the min, add one" selects the multiset {(L_j + t, j) : t >= 0} in
    ascending (value, j) order — the block smallest entries of the
    (W_pad, block) value matrix flattened j-major, via lax.top_k on the
    negated values (top_k surfaces the lowest flat index first on ties, so
    ties land on the lowest worker, then ascending t, matching argmin's
    first-index rule at every step).  Loads are integer counts in f32, so
    values and ties are IEEE-exact.  The oracle uses this formulation; the
    kernels use `waterfill_levels` and `waterfill_assign`, which Mosaic can
    lower.

    With `inv_cap` (a (1, n_workers) reciprocal-capacity row, arXiv
    1705.09073) the argmin runs over capacity-normalized values
    ``(L_j + t) / c_j`` — computed as ``(L_j + t) * inv_cap_j``, the SAME
    float product the sequential host scan forms, so block=1 stays
    bit-exact to the host and any block stays exact vs the kernels' loop.
    The multiset argument is unchanged: values still increase
    strictly in t for every worker (inv_cap > 0).  A uniform inv_cap of
    1.0 multiplies exactly and reproduces the unweighted picks bit-for-bit.

    Returns picks (block,) int32 worker ids.
    """
    row, icap, w_pad = _padded_row(loads, inv_cap, n_workers)
    t = jnp.arange(block, dtype=jnp.float32)
    vals = row.reshape(w_pad, 1) + t[None, :]  # (W_pad, B): (j, t)
    if icap is not None:
        vals = vals * icap.reshape(w_pad, 1)
    _, idx = lax.top_k(-vals.reshape(-1), block)  # ties -> j-major
    return (idx // block).astype(jnp.int32)


def head_table_ncand(kb, tk, tn, d_base, d_max):
    """Per-lane candidate count from a head-table snapshot: (V, H) equality
    compare + masked max (no gather); a miss or a tail hit yields d_base.
    A W_SENTINEL table entry (any_worker head tables) passes through
    unclipped, flagging the global-argmin path to route_block."""
    hit = kb[:, None] == tk[None, :]  # (V, H)
    nc = jnp.max(jnp.where(hit, tn, 0), axis=1)  # (V,) 0 on miss
    clipped = jnp.clip(jnp.where(nc > 0, nc, d_base), d_base, d_max)
    return jnp.where(nc == jnp.int32(W_SENTINEL), nc, clipped)


def _mask_and_flag(lc, nc, d_max: int, w_mode: bool):
    """Shared mask step: candidate lane j of a row participates iff
    j < nc (W-sentinel rows keep all d_max tail lanes live under w_mode, the
    global pick overrides below).  nc=None means every lane participates
    (plain fixed-d routing) — no mask is materialised at all."""
    if nc is None:
        return lc, None
    is_w = nc == jnp.int32(W_SENTINEL)
    nc_tail = jnp.where(is_w, d_max, nc) if w_mode else nc
    col = jnp.arange(d_max, dtype=jnp.int32)
    return jnp.where(col[None, :] < nc_tail[:, None], lc, jnp.float32(MASK)), is_w


def pick_lane(x, sel):
    """x[i, sel[i]] for (V, d) x and (V,) sel, as a masked select over the d
    lanes: Mosaic has no gather, and one live lane plus zeros sums exactly."""
    col = jnp.arange(x.shape[-1], dtype=jnp.int32)
    return jnp.sum(jnp.where(col[None, :] == sel[:, None], x, 0), axis=-1)


def waterfill_assign(loads, is_w, *, n_workers, inv_cap=None):
    """Water-fill destinations of a block's head lanes, computed by the
    definition itself: for each head lane in lane order, take the masked
    global argmin of the running loads row and add one to it.

    loads (1, n_workers) f32 block-start row, is_w (V,) bool head flags,
    inv_cap optional (1, n_workers) reciprocal capacities.  Returns (V,)
    int32; lanes that are not heads read 0.  The row is padded to a LANES
    multiple with the MASK sentinel (pad lanes never win), ties break to
    the lowest worker index, and the capacity-normalized value is the same
    ``(L_j + t) * inv_cap_j`` product the host scan forms.  The loop runs V
    steps of lane reductions and selects only, so it compiles under Mosaic
    (no top_k, sort, cumsum or gather).  route_block runs it only with
    capacities, whose values are not integers; without them it runs the
    loop-free `waterfill_levels`.
    """
    V = is_w.shape[0]
    row, icap, w_pad = _padded_row(loads, inv_cap, n_workers)
    wid = lax.broadcasted_iota(jnp.int32, (1, w_pad), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, V), 1)

    def body(_, c):
        cnt, todo, out = c  # (1, w_pad) f32, (1, V) i32, (1, V) i32
        vals = row + cnt
        if icap is not None:
            vals = vals * icap
        m = jnp.min(vals, axis=1, keepdims=True)
        j = jnp.min(jnp.where(vals == m, wid, w_pad), axis=1, keepdims=True)
        first = jnp.min(jnp.where(todo > 0, lane, V), axis=1, keepdims=True)
        hit = lane == first  # the lowest head lane still unassigned
        return (
            cnt + (wid == j).astype(jnp.float32),
            jnp.where(hit, 0, todo),
            jnp.where(hit, j, out),
        )

    init = (
        jnp.zeros((1, w_pad), jnp.float32),
        is_w.astype(jnp.int32).reshape(1, V),
        jnp.zeros((1, V), jnp.int32),
    )
    return lax.fori_loop(0, V, body, init)[2].reshape(V)


def waterfill_levels(loads, is_w, *, n_workers):
    """`waterfill_assign` without capacities and without a loop: the same
    (V,) int32 destinations, found by counting picks per load level.

    It needs integer loads (counts in f32, or MASK): a fractional row,
    such as the sharded router's weighted load-sync leaves, would truncate
    in D below and pick other workers than the definition; such callers
    hand route_block an inv_cap row of ones, which takes `waterfill_assign`.
    With integer loads every value the water-fill compares is an integer.
    Worker j's t-th pick happens at level D_j + t, D_j = L_j - min(L), and
    level s holds one pick of each worker with D_j <= s, in index order
    (argmin's lowest-index rule).  The least-loaded worker alone supplies
    one pick per level, so the first V picks lie below level V and D clips
    at V (pad and masked lanes clip there too).  For head lane i of rank r
    (heads before it in lane order):

      cle[s]  = sum_j max(s + 1 - D_j, 0)      picks at levels <= s
      lvl     = #{s : cle[s] <= r}             the level of pick r
      off     = r - sum_j max(lvl - D_j, 0)    its place within the level
      pick    = the off-th worker (index order) with D_j <= lvl

    The place within a level is an exclusive prefix count over workers, a
    matmul of 0/1 bf16 operands into an f32 accumulator, so exact.  Only
    compares, selects, lane and sublane sums and one matmul: no top_k, sort,
    cumsum or gather, so it lowers under Mosaic as route_block does.  A row
    whose every lane is masked gives 0, as the definition does there (MASK
    + t rounds to MASK, so the lowest index wins every pick).
    """
    V = is_w.shape[0]
    row, _, w_pad = _padded_row(loads, None, n_workers)
    m = jnp.min(row, axis=1, keepdims=True)
    d_row = jnp.minimum(row - m, V).astype(jnp.int32)  # (1, w_pad)
    wi = lax.broadcasted_iota(jnp.int32, (w_pad, w_pad), 0)
    wj = lax.broadcasted_iota(jnp.int32, (w_pad, w_pad), 1)
    d_col = jnp.sum(jnp.where(wi == wj, d_row, 0), axis=1, keepdims=True)
    s_row = lax.broadcasted_iota(jnp.int32, (1, V), 1)
    cle = jnp.sum(jnp.maximum(s_row + 1 - d_col, 0), axis=0, keepdims=True)
    lane_i = lax.broadcasted_iota(jnp.int32, (V, V), 0)
    lane_k = lax.broadcasted_iota(jnp.int32, (V, V), 1)
    heads = is_w.astype(jnp.int32).reshape(1, V)
    rank = jnp.sum(jnp.where(lane_k < lane_i, heads, 0), axis=1, keepdims=True)
    lvl = jnp.sum((cle <= rank).astype(jnp.int32), axis=1, keepdims=True)
    off = rank - jnp.sum(jnp.maximum(lvl - d_row, 0), axis=1, keepdims=True)
    le = d_row <= lvl  # (V, w_pad): the workers with a pick at lvl
    before = jnp.dot(
        le.astype(jnp.bfloat16), (wi < wj).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    hit = le & (before == off) & (m < MASK)
    wid = lax.broadcasted_iota(jnp.int32, (V, w_pad), 1)
    pick = jnp.min(jnp.where(hit, wid, w_pad), axis=1)
    return jnp.where(is_w & (pick < w_pad), pick, 0)


def route_block(cand, nc, loads, *, n_entities, w_mode, inv_cap=None):
    """The kernel-side masked-greedy routing core for one vector block.

    cand (V, d_max) int32 candidate entity ids, nc (V,) int32 candidate
    counts (or None: all d_max lanes live), loads (1, n_entities) f32.
    Returns (choice (V,), sel (V,), is_w (V,) or None, new loads).  `sel` is
    the winning candidate column (MoE dispatch selects the matching gate with
    it); `is_w` flags the lanes the W path overrode (their `sel` is
    meaningless).  Every device router calls this — the callers differ ONLY
    in how cand/nc are produced — so sentinel/tie-break/update semantics
    cannot drift apart.

    Loads are fetched and written back MXU-style: one-hot(cand) @ loads for
    the candidate lookup, ones @ one-hot(choice) for the histogram update —
    no gathers or scatters (DESIGN.md SS2/SS7).  The fetch, the lane masks
    and the candidate argmin run in the `candidate_fetch` named scope.

    `inv_cap` (optional (1, n_entities) f32 reciprocal-capacity row) makes
    every comparison capacity-normalized: the fetch reads the normalized
    row ``loads * inv_cap`` and the water-fill receives inv_cap, while the
    CARRY stays the raw integer-count histogram (the +1 update is exact and
    capacity only ever rescales comparisons).  inv_cap=None skips the
    multiply entirely — the program is unchanged — and a uniform row of 1.0
    multiplies exactly, so both are bit-identical to the unweighted kernel.

    With w_mode (static), lanes with nc == W_SENTINEL take the W-Choices
    path: the r-th such lane of the block gets the r-th water-fill argmin of
    the block-start loads row, so consecutive head messages spread exactly
    as the sequential global-argmin would.  The picks come from the
    loop-free level count (waterfill_levels) without capacities, and from
    the sequential waterfill_assign with them (inv_cap is static, so each
    trace holds one of the two).  The level count needs integer loads, so a
    caller whose loads can be fractional passes inv_cap (ones at least).
    Tail lanes still read block-start loads
    only — the same < block staleness contract as the load vector itself
    (DESIGN.md SS2).  w_mode=False skips the water-fill entirely for callers
    that never emit the sentinel; sentinel-free streams route identically
    either way.
    """
    V, d_max = cand.shape
    eid = jnp.arange(n_entities, dtype=jnp.int32)
    with jax.named_scope("candidate_fetch"):
        onehot_c = (cand[..., None] == eid).astype(jnp.float32)  # (V, d_max, n)
        row = loads if inv_cap is None else loads * inv_cap
        # HIGHEST: a TPU f32 matmul otherwise runs one bf16 pass, which rounds
        # any load above 256 (8 mantissa bits); full f32 fetches it exactly
        lc = jax.lax.dot_general(
            onehot_c.reshape(V * d_max, n_entities),
            row.reshape(n_entities, 1),
            (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).reshape(V, d_max)
        lc, is_w = _mask_and_flag(lc, nc, d_max, w_mode)
        # argmin with ties -> first candidate, as min + lowest matching column
        col = lax.broadcasted_iota(jnp.int32, (V, d_max), 1)
        m = jnp.min(lc, axis=-1, keepdims=True)
        sel = jnp.min(jnp.where(lc == m, col, d_max), axis=-1)
        choice = pick_lane(cand, sel)
    if w_mode:
        with jax.named_scope("waterfill"):
            if inv_cap is None:
                head_choice = waterfill_levels(
                    loads, is_w, n_workers=n_entities
                )
            else:
                head_choice = waterfill_assign(
                    loads, is_w, n_workers=n_entities, inv_cap=inv_cap
                )
        choice = jnp.where(is_w, head_choice, choice)
    hist = (choice[:, None] == eid).astype(jnp.float32).sum(axis=0)
    return choice, sel, is_w, loads + hist[None, :]


def oracle_block_step(loads, cand, nc, *, n_entities, w_mode, inv_cap=None):
    """The host-side (gather-based) twin of route_block — one vector block of
    the masked batch-greedy, shared by every ref.py oracle and the host MoE
    router modes.  loads (n_entities,) f32, cand (V, d_max), nc (V,) or None,
    inv_cap (n_entities,) f32 reciprocal capacities or None.
    Returns (new_loads, choice, sel, is_w).

    The fetch is a plain gather (loads[cand]) and the W pick a plain indexed
    read — deliberately a DIFFERENT formulation from the kernel's one-hot
    matmuls, so the differential tests check the MXU formulation against
    straightforward indexing while the mask/sentinel/tie-break logic stays
    shared (same _mask_and_flag).  The W picks come from the loop-free
    waterfill_picks, the top_k twin of the kernel's waterfill_levels and
    waterfill_assign."""
    d_max = cand.shape[-1]
    row = loads if inv_cap is None else loads * inv_cap
    lc = row[cand]  # (V, d_max)
    lc, is_w = _mask_and_flag(lc, nc, d_max, w_mode)
    sel = jnp.argmin(lc, axis=-1)
    choice = jnp.take_along_axis(cand, sel[:, None], axis=-1)[:, 0]
    if w_mode:
        rank = jnp.cumsum(is_w.astype(jnp.int32)) - is_w
        picks = waterfill_picks(
            loads[None, :], n_workers=n_entities, block=cand.shape[0],
            inv_cap=None if inv_cap is None else inv_cap[None, :],
        )
        choice = jnp.where(is_w, picks[rank], choice)
    hist = jax.nn.one_hot(choice, n_entities, dtype=jnp.float32).sum(0)
    return loads + hist, choice, sel, is_w
