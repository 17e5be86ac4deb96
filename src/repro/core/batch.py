"""Batched element-ops dispatch layer for the forest hot loops.

The paper's New/Adapt/Balance/Ghost pipelines spend essentially all their
time in constant-time element queries (parent, children, face-neighbor,
successor, encode/decode — Sections 4.5-4.6).  This module is the single
seam through which the forest layer reaches that math, with three
interchangeable backends over `Simplex` batches:

  reference   the existing `SimplexOps` methods, dispatched eagerly op by op
              (the seed's behaviour; every intermediate materialises).
  jnp         the same algorithms under `jax.jit` with power-of-two padding
              buckets, so each op is one fused XLA program and the number of
              distinct compiled shapes stays O(log n).
  pallas      the tiled Pallas kernels from `repro.kernels` (interpret mode
              on CPU, compiled tiles on TPU).

All three produce bit-identical integer results; the backend knob trades
dispatch overhead against compile time.  Select globally via the
``REPRO_BACKEND`` env var, `set_backend()`, or the `use_backend()` context
manager.  Unknown names fall back to `reference` with a warning.  A known
backend is never swapped for another: a `pallas` kernel that cannot lower
on this platform raises where it is first dispatched, so a run labelled
`pallas` always ran the Pallas kernels.

Future scaling PRs (sharding, multi-device partition) plug in here: a new
backend only has to implement the `BatchedOps` method surface (the eight
per-element algorithms, the cross-tree `tree_transform`, and the
marker-table `owner_rank` searchsorted that routes the message-based
Balance/Ghost).
"""

from __future__ import annotations

import contextlib
import functools
import os
import warnings
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from . import u64 as u64m
from .ops import ElementOps, get_ops
from .types import ECLASS_SIMPLEX, Simplex

__all__ = [
    "BACKENDS",
    "BatchedOps",
    "FaceSweep",
    "SweepHandle",
    "LeafTable",
    "RoutePairs",
    "get_backend",
    "set_backend",
    "use_backend",
    "get_batch_ops",
    "dispatch_counts",
    "reset_dispatch_counts",
    "count_dispatch",
    "trace_counts",
    "reset_trace_counts",
    "host_fetch_counts",
    "reset_host_fetch_counts",
    "fetch_span",
]

BACKENDS = ("reference", "jnp", "pallas")
_ENV_VAR = "REPRO_BACKEND"
_active: str | None = None  # resolved lazily so the env var can be set late


def _resolve(name: str, source: str) -> str:
    name = name.strip().lower()
    if name not in BACKENDS:
        warnings.warn(
            f"unknown element-ops backend {name!r} from {source}; "
            f"falling back to 'reference' (choices: {BACKENDS})",
            stacklevel=3,
        )
        return "reference"
    return name


def get_backend() -> str:
    """The active backend name (env var ``REPRO_BACKEND``, default reference)."""
    global _active
    if _active is None:
        _active = _resolve(os.environ.get(_ENV_VAR, "reference"), f"${_ENV_VAR}")
    return _active


def set_backend(name: str) -> None:
    global _active
    _active = _resolve(name, "set_backend()")


@contextlib.contextmanager
def use_backend(name: str):
    """Temporarily switch the element-ops backend (tests / benchmarks)."""
    global _active
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        _active = prev


# ------------------------------------------------------------------ counters
# Views over the program's counter registry (`repro.core.telemetry`), with
# the keys and values they always had:
#
#   dispatch.<op>   one per BatchedOps op invocation (any backend): Balance/
#                   Ghost evaluation must issue ONE `face_sweep` dispatch per
#                   eval layer instead of 3 x (d+1) per-face ops.
#   trace.<prog>    one per *jit trace* of a fused-eval program (bumped inside
#                   the traced body, so cache hits cost nothing): with padded
#                   power-of-two buckets the totals stay O(log n), and the
#                   retrace guard asserts zero new traces when Balance re-runs
#                   at the same bucket sizes.
#   fetch.<stage>   one per device->host materialization of the fused eval
#                   (`eval_2to1` / `eval_cache` / `eval_route` each fetch ONE
#                   compacted result): at most 2 per rank per Balance round.
#
# Benchmarks and tests read and reset them around a measured region.
def reset_dispatch_counts() -> None:
    """Zero the per-op dispatch counters."""
    telemetry.reset_counts("dispatch.")


def dispatch_counts() -> dict[str, int]:
    """Snapshot of {op name: number of BatchedOps dispatches} since reset."""
    return telemetry.counts("dispatch.")


def count_dispatch(name: str) -> None:
    """Charge one dispatch to `name` without dispatching: callers that
    memoize a batched-op result on immutable data (e.g. the per-Forest
    resident sweep) keep the meters' evals-per-round semantics by charging
    each reuse like the dispatch it replaces."""
    telemetry.count("dispatch." + name)


def reset_trace_counts() -> None:
    """Zero the fused-eval jit trace counters."""
    telemetry.reset_counts("trace.")


def trace_counts() -> dict[str, int]:
    """Snapshot of {program name: jit traces} since reset."""
    return telemetry.counts("trace.")


def reset_host_fetch_counts() -> None:
    """Zero the fused-eval host materialization counters."""
    telemetry.reset_counts("fetch.")


def host_fetch_counts() -> dict[str, int]:
    """Snapshot of {eval stage: device->host materializations} since reset."""
    return telemetry.counts("fetch.")


def fetch_span(site: str, **stats):
    """The `fetch` span around one device->host materialization on the
    cycle's path: the host waits there for the device programs the fetched
    arrays come from.  Dispatch before entering it, so the span holds the
    wait and the copy only."""
    return telemetry.span("fetch", site=site, **stats)


class FaceSweep(NamedTuple):
    """Result of the fused all-faces sweep, leading axis = face (nf rows:
    d+1 for simplices, 2d for hexes).

    neighbor  same-level neighbor per face: anchor (nf, n, d), level/stype
              (nf, n) — possibly outside the root (check `inside`)
    dual      (nf, n) int32 neighbor's face index back to us
    inside    (nf, n) bool inside-root mask
    key       (nf, n) U64 neighbor morton keys (garbage where ~inside on a
              domain boundary — never read them there)
    """

    neighbor: Simplex
    dual: jax.Array
    inside: jax.Array
    key: u64m.U64


# ---------------------------------------------------------------- jnp backend
def _bucket(n: int) -> int:
    """Next power-of-two batch size (>= 16): bounds jit recompiles to O(log n)."""
    return max(16, 1 << max(0, n - 1).bit_length())


def _pad1(a, m):
    return jnp.pad(a, [(0, m - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _pad_simplex(s: Simplex, m: int) -> Simplex:
    return Simplex(_pad1(s.anchor, m), _pad1(s.level, m), _pad1(s.stype, m))


def _face_sweep_fused(o: ElementOps):
    """One jitted program for the whole face sweep: (face_neighbor,
    is_inside_root, morton_key) mapped over the nf face indices — a single
    XLA dispatch instead of 3 x nf.  The faces run one after another
    (`lax.map`, not `vmap`): batching all nf at once holds every face's
    temporaries together, which at 2^23 elements outgrows a 16 GB chip."""

    def fn(s: Simplex) -> FaceSweep:
        def one(f):
            nb, dual = o.face_neighbor(s, f)
            return FaceSweep(nb, dual, o.is_inside_root(nb), o.morton_key(nb))

        return jax.lax.map(one, jnp.arange(o.nf, dtype=jnp.int32))

    return fn


@functools.lru_cache(maxsize=None)
def _jnp_fns(d: int, eclass: int = ECLASS_SIMPLEX):
    o = get_ops(d, eclass)

    def parent_and_local_index(s):
        return o.parent(s), o.local_index(s)

    return {
        "morton_key": jax.jit(o.morton_key),
        "decode": jax.jit(o.decode_key),
        "parent": jax.jit(o.parent),
        "parent_and_local_index": jax.jit(parent_and_local_index),
        "children": jax.jit(o.children_tm),
        "face_neighbor": jax.jit(o.face_neighbor),
        "face_sweep": jax.jit(_face_sweep_fused(o)),
        "successor": jax.jit(o.successor),
        "is_inside_root": jax.jit(o.is_inside_root),
        "local_index": jax.jit(o.local_index),
        "tree_transform": jax.jit(o.tree_transform),
    }


def _pad_markers(marker_tree: np.ndarray, marker_key: np.ndarray):
    """Pad the per-rank marker table to a power of two (>= 8) with lex-+inf
    sentinels (tree = int32 max) so compiled shapes stay O(log P) and padding
    never counts in the searchsorted."""
    P = len(marker_tree)
    m = max(8, 1 << max(0, P - 1).bit_length())
    mt = np.full(m, np.iinfo(np.int32).max, np.int32)
    mk = np.zeros(m, np.uint64)
    mt[:P] = marker_tree
    mk[:P] = marker_key
    return mt, mk


# Memoized pad + device transfer of the marker table, keyed on CONTENT (the
# marker bytes): every Balance round calls `owner_rank` many times with the
# same P-entry table, and re-padding/re-uploading it per call was pure
# overhead.  The previous identity key (`id(mt), id(mk)`) silently served
# stale device markers to a caller that mutated a table in place (identity
# unchanged, content changed) — the content key closes that hole and also
# dedupes equal-content tables that arrive as fresh arrays.  Hashing P
# entries per call is O(P) host work, noise next to one dispatch.
_marker_pad_cache: OrderedDict = OrderedDict()
_MARKER_CACHE_SIZE = 16

# Same idea for the per-rank boundary scalars of the fused eval programs
# (8 device scalars per (markers, rank)) and the rank-id scalar.
_boundary_scalar_cache: OrderedDict = OrderedDict()
_rank_scalar_cache: dict[int, jax.Array] = {}


def _rank_scalar(g: int):
    hit = _rank_scalar_cache.get(g)
    if hit is None:
        hit = _rank_scalar_cache[g] = jnp.int32(g)
    return hit


def _padded_markers_cached(mt: np.ndarray, mk: np.ndarray):
    """(device marker_tree, device marker_key U64), padded with sentinels."""
    key = (mt.tobytes(), mk.tobytes())
    hit = _marker_pad_cache.get(key)
    if hit is not None:
        _marker_pad_cache.move_to_end(key)
        return hit
    mt_p, mk_p = _pad_markers(mt, mk)
    val = (jnp.asarray(mt_p), u64m.from_int(mk_p))
    _marker_pad_cache[key] = val
    while len(_marker_pad_cache) > _MARKER_CACHE_SIZE:
        _marker_pad_cache.popitem(last=False)
    return val


def owner_rank_lex(t, hi, lo, mt, mhi, mlo):
    """The one shared lex searchsorted: index of the last marker (mt, mhi,
    mlo) lex-<= (t, hi, lo), clamped to 0.  The jnp backend jits exactly
    this; `repro.kernels.ref.owner_rank_ref` delegates here so the Pallas
    kernel's oracle can never drift from the backend implementations."""
    le = (mt[None, :] < t[:, None]) | (
        (mt[None, :] == t[:, None])
        & ((mhi[None, :] < hi[:, None])
           | ((mhi[None, :] == hi[:, None]) & (mlo[None, :] <= lo[:, None])))
    )
    return jnp.maximum(le.astype(jnp.int32).sum(axis=1) - 1, 0)


_owner_rank_jnp = jax.jit(owner_rank_lex)


def lex_lower_bound(lt, lhi, llo, qt, qhi, qlo):
    """For each query (qt, qhi, qlo), the index of the first row of the
    lex-sorted table (lt, lhi, llo) that is lex->= it, or the table's
    length when none is: `searchsorted(side="left")` over (tree, key), with
    the uint64 keys carried as (hi, lo) uint32 words.  Queries are 1-D.

    One merged sort answers every query at once: the queries go first and
    the sort is stable, so a query lands before any table row equal to it,
    and the table rows ahead of it in merged order are exactly its lower
    bound.  A second sort, on each merged row's origin, brings those counts
    back to query order.  A TPU sorts a batch far faster than it gathers
    element by element, which a binary search does log2(len(lt)) times per
    query."""
    q = qt.shape[0]
    origin = jnp.arange(q + lt.shape[0], dtype=jnp.int32)
    *_, origin = jax.lax.sort(
        (jnp.concatenate([qt, lt]), jnp.concatenate([qhi, lhi]),
         jnp.concatenate([qlo, llo]), origin),
        num_keys=3, is_stable=True)
    is_row = (origin >= q).astype(jnp.int32)
    rows_before = jnp.cumsum(is_row) - is_row
    _, rows_before = jax.lax.sort((origin, rows_before), num_keys=1)
    return rows_before[:q]


# ------------------------------------------------------- device-resident eval
# The fused Balance/Ghost eval stage.  A round's evaluation is three device
# programs over ONE resident face sweep — need-mask vs the local leaf table,
# need-mask vs the remote-leaf cache, and boundary query routing — with the
# host only slicing the compacted routing rows to build wire triples.  The
# reference backend runs the same algorithms eagerly in numpy and is the
# bit-identical oracle.


class SweepHandle(NamedTuple):
    """One face sweep of an element layer, resident where the backend
    computes: `host` numpy arrays under `reference`, bucket-padded device
    arrays under `jnp`/`pallas` (stable shapes, so the fused eval programs
    never retrace across Balance rounds at a fixed bucket).

      host  (tgt, nkey, valid, dual, level): target tree (d+1, n) int32,
            neighbor keys (d+1, n) uint64, validity mask (d+1, n) bool,
            dual faces (d+1, n) int32, element levels (n,) int32
      dev   (tgt, khi, klo, valid, dual, level) padded to bucket m, the
            uint64 keys carried as (hi, lo) uint32 words
    """

    n: int
    host: tuple | None
    dev: tuple | None


class LeafTable(NamedTuple):
    """A lex-sorted (tree, key, level) leaf table — the local leaves or the
    remote-leaf cache — uploaded once per Balance round.  `host` feeds the
    reference oracle; `dev` is padded to a power of two with lex-+inf
    sentinels (tree = int32 max, level = -1), which sort after every leaf
    in the device's merged sort (`lex_lower_bound`) and never count as a
    finer leaf."""

    n: int
    host: tuple | None
    dev: tuple | None


class RoutePairs(NamedTuple):
    """Compacted query candidates from `eval_route`: one row per (face,
    element) pair whose neighbor key interval reaches outside the calling
    rank's partition — the ONLY sweep data the host slices off device on
    the routing path."""

    tree: np.ndarray
    key: np.ndarray
    level: np.ndarray
    dual: np.ndarray
    first: np.ndarray
    last: np.ndarray


def _empty_route() -> RoutePairs:
    z = np.zeros(0, np.int32)
    return RoutePairs(z, np.zeros(0, np.uint64), z.copy(), z.copy(), z.copy(), z.copy())


def _spans_np(d: int, L: int, level: np.ndarray) -> np.ndarray:
    """Keys covered by one element at `level`: 2^(d*(L-level)), uint64."""
    return np.uint64(1) << (
        np.uint64(d) * (np.uint64(L) - np.asarray(level).astype(np.uint64))
    )


def _range_max_np(values: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Per-slice max(values[lo:hi]) (or -1 for empty slices), vectorized via
    maximum.reduceat over independent [lo, hi) segment pairs."""
    out = np.full(len(lo), -1, np.int32)
    m = hi > lo
    if not m.any():
        return out
    ext = np.append(np.asarray(values, np.int32), np.int32(-1))  # allow hi == len
    idx = np.nonzero(m)[0]
    pairs = np.stack([lo[idx], hi[idx]], axis=1).reshape(-1)
    out[idx] = np.maximum.reduceat(ext, pairs)[::2]
    return out


def _owner_np(tree: np.ndarray, key: np.ndarray, mt: np.ndarray, mk: np.ndarray):
    """Host mirror of `owner_rank_lex` over uint64 keys."""
    le = (mt[None, :] < tree[:, None]) | (
        (mt[None, :] == tree[:, None]) & (mk[None, :] <= key[:, None])
    )
    return np.maximum(le.sum(axis=1).astype(np.int32) - 1, 0)


@functools.lru_cache(maxsize=None)
def _eval_progs(d: int, eclass: int = ECLASS_SIMPLEX):
    """The jitted device programs of the fused eval stage, per (dimension,
    element class).

    Every program takes padded buffers only — element buffers quantized to
    `_bucket` sizes, leaf tables and markers to their own power-of-two pads
    — so the set of compiled shapes is O(log n) for the life of the process
    (`trace_counts()` observes it; the device_eval suite asserts it)."""
    o = get_ops(d, eclass)
    L = o.L
    nf = o.nf

    def lex_lt(t1, h1, l1, t2, h2, l2):
        return (t1 < t2) | (
            (t1 == t2) & ((h1 < h2) | ((h1 == h2) & (l1 < l2)))
        )

    def interval_end(khi, klo, lev2):
        # Element keys are span-aligned, so the last key of the neighbor's
        # interval is key | (2^(d*(L-level)) - 1) — a dynamic-width mask
        # from O(log) selects over the (hi, lo) words.
        sb = d * (L - lev2)
        one = u64m.U64(jnp.zeros_like(khi), jnp.full_like(klo, 1))
        mask = u64m.dec(u64m.select_shl(one, sb, 63))
        return u64m.or_(u64m.U64(khi, klo), mask)

    def finer_mask(tgt, khi, klo, lev2, kend, lt, lhi, llo, llev):
        # cnt[thr, j] = #leaves among the first j with level >= thr, so the
        # "any strictly-finer-than-l+1 leaf in the interval" test is one
        # subtraction per (face, element) pair.  Sentinel rows carry
        # level = -1 and never count; thr is clamped to L + 1 so levels
        # >= L - 1 (which can never have a 2-finer leaf) read a zero row.
        rows = jnp.arange(L + 2, dtype=jnp.int32)
        incr = (llev[None, :] >= rows[:, None]).astype(jnp.int32)
        cnt = jnp.concatenate(
            [jnp.zeros((L + 2, 1), jnp.int32), jnp.cumsum(incr, axis=1)], axis=1
        )
        # The interval's first and one-past-last leaf, both bounds of every
        # pair found by one merged sort with the table.
        t = tgt.reshape(-1)
        bounds = lex_lower_bound(
            lt, lhi, llo, jnp.concatenate([t, t]),
            jnp.concatenate([khi.reshape(-1), kend.hi.reshape(-1)]),
            jnp.concatenate([klo.reshape(-1), kend.lo.reshape(-1)]))
        lo_i, hi_i = bounds.reshape((2,) + tgt.shape)
        thr = jnp.minimum(lev2 + 2, L + 1)
        return (cnt[thr, hi_i] - cnt[thr, lo_i]) > 0

    def off_mask(tgt, khi, klo, kh, b0t, b0h, b0l, h0, b1t, b1h, b1l, h1):
        # Interval escapes this rank's partition range [marker_g,
        # marker_{g+1}): lex (t, k) below the lower marker, or lex
        # (t, k_end) at/above the upper one.  h0/h1 gate the domain ends.
        off = h0 & lex_lt(tgt, khi, klo, b0t, b0h, b0l)
        return off | (h1 & ~lex_lt(tgt, kh.hi, kh.lo, b1t, b1h, b1l))

    def sweep(s, tree, n):
        telemetry.count("trace.sweep")
        m = s.level.shape[0]
        sw = _face_sweep_fused(o)(s)
        valid = sw.inside & (jnp.arange(m) < n)[None, :]
        tgt = jnp.broadcast_to(tree[None, :], (nf, m))
        return tgt, sw.key.hi, sw.key.lo, valid, sw.dual, s.level

    def sweep_pallas(s, tree, n):
        telemetry.count("trace.sweep_pallas")
        from repro.kernels import ops as kops

        m = s.level.shape[0]
        nb, dual, inside, key = kops.face_sweep(d, s, min(1024, m), eclass)
        valid = inside & (jnp.arange(m) < n)[None, :]
        tgt = jnp.broadcast_to(tree[None, :], (nf, m))
        return tgt, key.hi, key.lo, valid, dual, s.level

    def eval_need(tgt, khi, klo, valid, lev,
                lt, lhi, llo, llev,
                b0t, b0h, b0l, h0, b1t, b1h, b1l, h1):
        telemetry.count("trace.eval_need")
        m = lev.shape[0]
        lev2 = jnp.broadcast_to(lev[None, :], (nf, m))
        kh = interval_end(khi, klo, lev2)
        kend = u64m.inc(kh)
        finer = finer_mask(tgt, khi, klo, lev2, kend, lt, lhi, llo, llev)
        need = jnp.any(valid & finer, axis=0)
        off = off_mask(tgt, khi, klo, kh, b0t, b0h, b0l, h0, b1t, b1h, b1l, h1)
        bmask = jnp.any(valid & off, axis=0)
        return need, bmask

    def eval_cache(tgt, khi, klo, valid, lev,
                 lt, lhi, llo, llev,
                 b0t, b0h, b0l, h0, b1t, b1h, b1l, h1):
        telemetry.count("trace.eval_cache")
        m = lev.shape[0]
        lev2 = jnp.broadcast_to(lev[None, :], (nf, m))
        kh = interval_end(khi, klo, lev2)
        kend = u64m.inc(kh)
        off = off_mask(tgt, khi, klo, kh, b0t, b0h, b0l, h0, b1t, b1h, b1l, h1)
        bmask = jnp.any(valid & off, axis=0)
        evalp = valid & bmask[None, :]
        finer = finer_mask(tgt, khi, klo, lev2, kend, lt, lhi, llo, llev)
        return jnp.any(evalp & finer, axis=0)

    def route_pack(t, khi, klo, lev, dual, first, last, remote):
        # Cumsum-scatter compaction: remote rows land densely at the front,
        # non-remote lanes dump into the extra row sz (never read — the
        # host slices [:count]).  Flattened C-order of the face-major
        # (nf, m) `remote` keeps the row order of the host oracle's
        # np.nonzero.  The running count is taken per face row plus the
        # rows before it: one cumsum over the flattened mask compiles
        # about 7x slower on a TPU at 2^23 elements.
        sz = t.shape[0]
        run = jnp.cumsum(remote.astype(jnp.int32), axis=1)
        total = run[:, -1]
        idx = (run + (jnp.cumsum(total) - total)[:, None] - 1).reshape(-1)
        scat = jnp.where(remote.reshape(-1), idx, jnp.int32(sz))
        cols = jnp.stack(
            [t, khi.astype(jnp.int32), klo.astype(jnp.int32),
             lev, dual, first, last], axis=1)
        packed = jnp.zeros((sz + 1, 7), jnp.int32).at[scat].set(cols)
        return total.sum(), packed

    def eval_route(tgt, khi, klo, valid, dual, lev, mt, mhi, mlo, g):
        telemetry.count("trace.eval_route")
        m = lev.shape[0]
        lev2 = jnp.broadcast_to(lev[None, :], (nf, m))
        kh = interval_end(khi, klo, lev2)
        tf, hf, lf = tgt.reshape(-1), khi.reshape(-1), klo.reshape(-1)
        first = owner_rank_lex(tf, hf, lf, mt, mhi, mlo)
        last = owner_rank_lex(
            tf, kh.hi.reshape(-1), kh.lo.reshape(-1), mt, mhi, mlo)
        remote = valid & ((first != g) | (last != g)).reshape(valid.shape)
        return route_pack(tf, hf, lf, lev2.reshape(-1), dual.reshape(-1),
                          first, last, remote)

    def eval_route_pallas(tgt, khi, klo, valid, dual, lev, mt, mhi, mlo, g):
        telemetry.count("trace.eval_route_pallas")
        from repro.kernels import ops as kops

        m = lev.shape[0]
        lev2 = jnp.broadcast_to(lev[None, :], (nf, m))
        _hh, _hl, first, last = kops.eval_route(
            d, tgt, khi, klo, lev2, mt, mhi, mlo, min(1024, m))
        remote = valid & ((first != g) | (last != g))
        first, last = first.reshape(-1), last.reshape(-1)
        return route_pack(tgt.reshape(-1), khi.reshape(-1), klo.reshape(-1),
                          lev2.reshape(-1), dual.reshape(-1),
                          first, last, remote)

    return {
        "sweep": jax.jit(sweep),
        "sweep_pallas": jax.jit(sweep_pallas),
        "need": jax.jit(eval_need),
        "cache": jax.jit(eval_cache),
        "route": jax.jit(eval_route),
        "route_pallas": jax.jit(eval_route_pallas),
    }


# -------------------------------------------------------------------- dispatch
class BatchedOps:
    """Backend-bound batched element ops over `Simplex` arrays of shape (n,).

    The methods mirror the paper's constant-time element algorithms (plus
    the cross-tree coordinate change of `repro.core.cmesh`); every forest
    hot loop (adapt's child generation and family-head scan, balance's and
    ghost's neighbor sweeps — across tree faces included) consumes exactly
    this surface.
    """

    def __init__(self, d: int, backend: str, eclass: int = ECLASS_SIMPLEX):
        backend = _resolve(backend, "get_batch_ops()")
        self.d = d
        self.eclass = eclass
        self.backend = backend
        self.ops: ElementOps = get_ops(d, eclass)
        self.nf = self.ops.nf

    # -- helpers -----------------------------------------------------------
    def _which(self, n: int, name: str | None = None) -> str:
        # Empty batches short-circuit to the eager path (a Pallas grid of 0
        # tiles is invalid, and there is nothing to fuse anyway).
        if name is not None:
            telemetry.count("dispatch." + name)
        return "reference" if n == 0 else self.backend

    def _jnp(self, name, s: Simplex, *extra):
        n = s.level.shape[0]
        m = _bucket(n)
        out = _jnp_fns(self.d, self.eclass)[name](_pad_simplex(s, m), *extra)
        return out, n

    @staticmethod
    def _cut(x, n):
        return jax.tree_util.tree_map(lambda a: a[:n], x)

    def _pallas(self, fn, s: Simplex, *extra):
        """Bucket-pad before the jit'd kernel wrapper (same O(log n) compiled
        shapes as the jnp path), then slice the outputs back."""
        n = s.level.shape[0]
        m = _bucket(n)
        return self._cut(
            fn(self.d, _pad_simplex(s, m), *extra, min(1024, m), self.eclass), n)

    # -- API ---------------------------------------------------------------
    def morton_key(self, s: Simplex) -> u64m.U64:
        """Level-padded consecutive index (the mixed-level SFC sort key)."""
        which = self._which(s.level.shape[0], "morton_key")
        if which == "reference":
            return self.ops.morton_key(s)
        if which == "jnp":
            out, n = self._jnp("morton_key", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        hi, lo = self._pallas(kops.morton_key, s)
        return u64m.U64(hi, lo)

    def morton_key_np(self, s: Simplex) -> np.ndarray:
        """Host-side uint64 keys (the forest's storage format)."""
        key = self.morton_key(s)
        with fetch_span("morton_key"):
            return u64m.to_np(key)

    def decode(self, key: u64m.U64, level) -> Simplex:
        """Algorithm 4.8 from a level-padded key (inverse of `morton_key`)."""
        level = jnp.asarray(level, jnp.int32)
        which = self._which(key.hi.shape[0], "decode")
        if which == "reference":
            return self.ops.decode_key(key, level)
        if which == "jnp":
            n = key.hi.shape[0]
            m = _bucket(n)
            padded = u64m.U64(_pad1(key.hi, m), _pad1(key.lo, m))
            return self._cut(
                _jnp_fns(self.d, self.eclass)["decode"](padded, _pad1(level, m)), n)
        from repro.kernels import ops as kops

        n = key.hi.shape[0]
        m = _bucket(n)
        padded = u64m.U64(_pad1(key.hi, m), _pad1(key.lo, m))
        return self._cut(
            kops.decode(self.d, padded, _pad1(level, m), min(1024, m), self.eclass), n
        )

    def parent(self, s: Simplex) -> Simplex:
        """Algorithm 4.3."""
        which = self._which(s.level.shape[0], "parent")
        if which == "reference":
            return self.ops.parent(s)
        if which == "jnp":
            out, n = self._jnp("parent", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        return self._pallas(kops.parent, s)

    def parent_and_local_index(self, s: Simplex):
        """Fused Algorithm 4.3 + Table 6: (parent, TM child index) in one
        pass — the pair every family scan needs together."""
        which = self._which(s.level.shape[0], "parent_and_local_index")
        if which == "reference":
            return self.ops.parent(s), self.ops.local_index(s)
        if which == "jnp":
            out, n = self._jnp("parent_and_local_index", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        return self._pallas(kops.parent_and_local_index, s)

    def children(self, s: Simplex) -> Simplex:
        """All 2^d children in TM order: batch shape (n, 2^d)."""
        which = self._which(s.level.shape[0], "children")
        if which == "reference":
            return self.ops.children_tm(s)
        if which == "jnp":
            out, n = self._jnp("children", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        return self._pallas(kops.children, s)

    def face_neighbor(self, s: Simplex, face):
        """Algorithm 4.6: (same-level neighbor, dual face)."""
        which = self._which(s.level.shape[0], "face_neighbor")
        if which == "reference":
            return self.ops.face_neighbor(s, jnp.int32(face))
        if which == "jnp":
            out, n = self._jnp("face_neighbor", s, jnp.int32(face))
            return self._cut(out, n)
        from repro.kernels import ops as kops

        face = jnp.asarray(face, jnp.int32)
        if face.ndim:
            face = _pad1(face, _bucket(s.level.shape[0]))
        return self._pallas(kops.face_neighbor, s, face)

    def _face_sweep_reference(self, s: Simplex) -> FaceSweep:
        """Eager per-face compose of (face_neighbor, is_inside_root,
        morton_key) — the oracle the fused paths must match bit for bit."""
        cols = [[] for _ in range(4)]
        for f in range(self.nf):
            nb, dual = self.ops.face_neighbor(s, jnp.int32(f))
            cols[0].append(nb)
            cols[1].append(dual)
            cols[2].append(self.ops.is_inside_root(nb))
            cols[3].append(self.ops.morton_key(nb))
        nbs, duals, insides, keys = cols
        return FaceSweep(
            Simplex(
                jnp.stack([x.anchor for x in nbs]),
                jnp.stack([x.level for x in nbs]),
                jnp.stack([x.stype for x in nbs]),
            ),
            jnp.stack(duals),
            jnp.stack(insides),
            u64m.U64(jnp.stack([k.hi for k in keys]),
                     jnp.stack([k.lo for k in keys])),
        )

    def face_sweep(self, s: Simplex) -> FaceSweep:
        """Fused all-faces sweep: (face_neighbor, is_inside_root, morton_key)
        for every face 0..nf-1 in ONE backend dispatch — the hot query of the
        Balance/Ghost eval loops (which previously issued 3 x nf separate
        dispatches per layer).  Results carry a leading face axis; slicing
        row f yields exactly what composing the three per-face ops would."""
        n = s.level.shape[0]
        which = self._which(n, "face_sweep")
        if which == "reference":
            return self._face_sweep_reference(s)
        m = _bucket(n)
        cut = functools.partial(jax.tree_util.tree_map, lambda a: a[:, :n])
        if which == "jnp":
            return cut(_jnp_fns(self.d, self.eclass)["face_sweep"](_pad_simplex(s, m)))
        from repro.kernels import ops as kops

        nb, dual, inside, key = kops.face_sweep(
            self.d, _pad_simplex(s, m), min(1024, m), self.eclass)
        return cut(FaceSweep(nb, dual, inside, key))

    def successor(self, s: Simplex) -> Simplex:
        """Batch Algorithm 4.10: next same-level element along the SFC."""
        which = self._which(s.level.shape[0], "successor")
        if which == "reference":
            return self.ops.successor(s)
        if which == "jnp":
            out, n = self._jnp("successor", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        return self._pallas(kops.successor, s)

    def is_inside_root(self, s: Simplex):
        """Section 4.4 inside-root test (Proposition 23 vs. the root simplex)."""
        which = self._which(s.level.shape[0], "is_inside_root")
        if which == "reference":
            return self.ops.is_inside_root(s)
        if which == "jnp":
            out, n = self._jnp("is_inside_root", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        return self._pallas(kops.is_inside_root, s)

    def local_index(self, s: Simplex):
        """TM child index within the parent (paper Table 6)."""
        which = self._which(s.level.shape[0], "local_index")
        if which == "reference":
            return self.ops.local_index(s)
        if which == "jnp":
            out, n = self._jnp("local_index", s)
            return self._cut(out, n)
        from repro.kernels import ops as kops

        return self._pallas(kops.local_index, s)

    def owner_rank(self, tree, key, marker_tree, marker_key) -> np.ndarray:
        """Owner-rank resolution for the message-based Balance/Ghost: the
        rank whose partition range [marker_r, marker_{r+1}) contains the lex
        (tree, key) — a vectorized searchsorted against the allgathered
        marker table (`forest.partition_markers`), clamped to rank 0 for
        keys before the global first element.  Host-side numpy in/out (the
        forest's routing tables live on the host); the jnp and pallas paths
        run the identical unrolled compare chain over (hi, lo) uint32 words.
        """
        tree = np.asarray(tree, np.int32)
        key = np.asarray(key, np.uint64)
        mt = np.asarray(marker_tree, np.int32)
        mk = np.asarray(marker_key, np.uint64)
        n = len(tree)
        which = self._which(n, "owner_rank")
        if which == "reference":
            le = (mt[None, :] < tree[:, None]) | (
                (mt[None, :] == tree[:, None]) & (mk[None, :] <= key[:, None])
            )
            return np.maximum(le.sum(axis=1).astype(np.int32) - 1, 0)
        mt_j, mkey = _padded_markers_cached(mt, mk)
        m = _bucket(n)
        t_p = _pad1(jnp.asarray(tree), m)
        k = u64m.from_int(key)
        hi, lo = _pad1(k.hi, m), _pad1(k.lo, m)
        if which == "jnp":
            out = _owner_rank_jnp(t_p, hi, lo, mt_j, mkey.hi, mkey.lo)
            return np.asarray(out[:n], np.int32)
        from repro.kernels import ops as kops

        out = kops.owner_rank(
            u64m.U64(hi, lo), t_p, (mt_j, mkey), min(1024, m))
        return np.asarray(out[:n], np.int32)

    # -- fused Balance/Ghost eval stage -------------------------------------
    def sweep_full(self, s: Simplex, tree_ids) -> SweepHandle | None:
        """Face-sweep an element layer and keep the result resident: ONE
        `face_sweep` dispatch whose eight fields never fan out to numpy on
        the device backends — the fused eval programs consume the handle
        directly and only `eval_route`'s compacted rows cross to the host."""
        n = int(s.level.shape[0])
        if n == 0:
            return None
        which = self._which(n, "face_sweep")
        tree_ids = np.asarray(tree_ids, np.int32)
        if which == "reference":
            sw = self._face_sweep_reference(s)
            tgt = np.broadcast_to(tree_ids, (self.nf, n)).copy()
            host = (tgt, u64m.to_np(sw.key), np.asarray(sw.inside),
                    np.asarray(sw.dual), np.asarray(s.level))
            return SweepHandle(n, host, None)
        m = _bucket(n)
        prog = "sweep" if which == "jnp" else "sweep_pallas"
        dev = _eval_progs(self.d, self.eclass)[prog](
            _pad_simplex(s, m), _pad1(jnp.asarray(tree_ids), m), jnp.int32(n))
        return SweepHandle(n, None, dev)

    def sweep_from_host(self, tgt, nkey, valid, dual, level) -> SweepHandle | None:
        """Wrap a host-computed sweep (the cmesh cross-tree path) as a
        resident handle — padding + one upload, no dispatch counted (the
        sweep itself was already dispatched by `face_sweep_layer`)."""
        n = int(np.asarray(level).shape[0])
        if n == 0:
            return None
        tgt = np.asarray(tgt, np.int32)
        nkey = np.asarray(nkey, np.uint64)
        valid = np.asarray(valid, bool)
        dual = np.asarray(dual, np.int32)
        level = np.asarray(level, np.int32)
        host = (tgt, nkey, valid, dual, level)
        if self.backend == "reference":
            return SweepHandle(n, host, None)
        m = _bucket(n)
        pad2 = ((0, 0), (0, m - n))
        dev = (
            jnp.asarray(np.pad(tgt, pad2)),
            jnp.asarray(np.pad((nkey >> np.uint64(32)).astype(np.uint32), pad2)),
            jnp.asarray(np.pad(nkey.astype(np.uint32), pad2)),
            jnp.asarray(np.pad(valid, pad2)),
            jnp.asarray(np.pad(dual, pad2)),
            jnp.asarray(np.pad(level, (0, m - n))),
        )
        return SweepHandle(n, host, dev)

    def upload_table(self, tree, keys, level) -> LeafTable | None:
        """Upload a lex-sorted (tree, key, level) leaf table for the fused
        eval programs (None for an empty table — callers skip the eval)."""
        tree = np.asarray(tree, np.int32)
        keys = np.asarray(keys, np.uint64)
        level = np.asarray(level, np.int32)
        n = len(level)
        if n == 0:
            return None
        host = (tree, keys, level)
        if self.backend == "reference":
            return LeafTable(n, host, None)
        m = _bucket(n)
        lt = np.full(m, np.iinfo(np.int32).max, np.int32)
        lhi = np.zeros(m, np.uint32)
        llo = np.zeros(m, np.uint32)
        llev = np.full(m, -1, np.int32)
        lt[:n] = tree
        lhi[:n] = (keys >> np.uint64(32)).astype(np.uint32)
        llo[:n] = keys.astype(np.uint32)
        llev[:n] = level
        dev = (jnp.asarray(lt), jnp.asarray(lhi),
               jnp.asarray(llo), jnp.asarray(llev))
        return LeafTable(n, host, dev)

    @staticmethod
    def _boundary_scalars(mt, mk, g: int, P: int):
        """The two partition markers bounding rank g, as traced device
        scalars (so changing ranks or markers never retraces the eval
        programs).  Content-cached: a Balance round calls this for every
        rank against the SAME marker table, and eight scalar device_puts
        per call were pure overhead."""
        ckey = (mt.tobytes(), mk.tobytes(), g, P)
        hit = _boundary_scalar_cache.get(ckey)
        if hit is not None:
            _boundary_scalar_cache.move_to_end(ckey)
            return hit

        def words(t, k):
            k = int(k)
            return (jnp.int32(int(t)), jnp.uint32(k >> 32),
                    jnp.uint32(k & 0xFFFFFFFF))

        lo = words(mt[g], mk[g]) if g > 0 else words(0, 0)
        hi = words(mt[g + 1], mk[g + 1]) if g + 1 < P else words(0, 0)
        val = (*lo, jnp.bool_(g > 0), *hi, jnp.bool_(g + 1 < P))
        _boundary_scalar_cache[ckey] = val
        while len(_boundary_scalar_cache) > 4 * _MARKER_CACHE_SIZE:
            _boundary_scalar_cache.popitem(last=False)
        return val

    def _bmask_ref(self, sw: SweepHandle, mt, mk, g: int, P: int) -> np.ndarray:
        """Host oracle of the boundary-adjacent mask: some valid face
        interval escapes [marker_g, marker_{g+1})."""
        tgt, nkey, valid, _dual, lev = sw.host
        bmask = np.zeros(sw.n, bool)
        fi, ei = np.nonzero(valid)
        if len(ei) == 0:
            return bmask
        span = _spans_np(self.d, self.ops.L, lev)
        t_v = tgt[fi, ei]
        k_lo = nkey[fi, ei]
        k_hi = k_lo + span[ei] - np.uint64(1)
        off = np.zeros(len(ei), bool)
        if g > 0:
            off |= (t_v < mt[g]) | ((t_v == mt[g]) & (k_lo < mk[g]))
        if g + 1 < P:
            off |= (t_v > mt[g + 1]) | ((t_v == mt[g + 1]) & (k_hi >= mk[g + 1]))
        bmask[ei[off]] = True
        return bmask

    def _need_ref(self, sw: SweepHandle, table: LeafTable,
                  pairs_mask: np.ndarray) -> np.ndarray:
        """Host oracle of the 2:1 need-mask: for each (face, element) pair
        in `pairs_mask`, is some leaf of `table` in the neighbor interval
        more than one level finer than the element?"""
        tgt, nkey, _valid, _dual, lev = sw.host
        need = np.zeros(sw.n, bool)
        tt, kk, ll = table.host
        span = _spans_np(self.d, self.ops.L, lev)
        for t in np.unique(tgt[pairs_mask]):
            fi, ei = np.nonzero(pairs_mask & (tgt == t))
            a, b = np.searchsorted(tt, [t, t + 1])
            keys_t = kk[a:b]
            lo = np.searchsorted(keys_t, nkey[fi, ei])
            hi = np.searchsorted(keys_t, nkey[fi, ei] + span[ei])
            upd = _range_max_np(ll[a:b], lo, hi) > lev[ei] + 1
            need[ei[upd]] = True
        return need

    def _eval_fetch(self, site: str, n: int, m: int):
        """Count one device 2:1 eval's fetch; return its fetch span, whose
        stats carry the (face, element) pairs the eval tests for real
        (`pairs_live`, nf x n) and runs on (`pairs_padded`, nf x m, the
        padded bucket)."""
        telemetry.count("fetch." + site)
        return fetch_span(site, pairs_live=self.nf * n, pairs_padded=self.nf * m)

    def eval_2to1(self, sw: SweepHandle | None, table: LeafTable | None,
                  mt, mk, g: int):
        """Fused interior 2:1 eval: (need, boundary) element masks from one
        resident sweep vs the local leaf table — one device program, one
        host materialization."""
        if sw is None or sw.n == 0:
            z = np.zeros(0, bool)
            return z, z.copy()
        mt = np.asarray(mt, np.int32)
        mk = np.asarray(mk, np.uint64)
        P = len(mt)
        which = self._which(sw.n, "eval_2to1")
        if which == "reference" or table is None:
            bmask = self._bmask_ref(sw, mt, mk, g, P)
            if table is None:
                return np.zeros(sw.n, bool), bmask
            need = self._need_ref(sw, table, sw.host[2])
            return need, bmask
        tgtD, khiD, kloD, validD, _dualD, levD = sw.dev
        need_d, bm_d = _eval_progs(self.d, self.eclass)["need"](
            tgtD, khiD, kloD, validD, levD, *table.dev,
            *self._boundary_scalars(mt, mk, g, P))
        need_d, bm_d = need_d[:sw.n], bm_d[:sw.n]
        with self._eval_fetch("eval_2to1", sw.n, levD.shape[0]):
            # owned copies: callers fold masks in place (jax views are read-only)
            return np.array(need_d), np.array(bm_d)

    def eval_cache(self, sw: SweepHandle | None, cache: LeafTable | None,
                   mt, mk, g: int) -> np.ndarray:
        """Fused remote-cache 2:1 eval: need-mask of boundary-adjacent
        elements vs the remote-leaf cache (the off-rank witnesses folded in
        by earlier rounds)."""
        if sw is None or sw.n == 0 or cache is None:
            return np.zeros(0 if sw is None else sw.n, bool)
        mt = np.asarray(mt, np.int32)
        mk = np.asarray(mk, np.uint64)
        P = len(mt)
        which = self._which(sw.n, "eval_cache")
        if which == "reference":
            bmask = self._bmask_ref(sw, mt, mk, g, P)
            if not bmask.any():
                return np.zeros(sw.n, bool)
            return self._need_ref(sw, cache, sw.host[2] & bmask[None, :])
        tgtD, khiD, kloD, validD, _dualD, levD = sw.dev
        need_d = _eval_progs(self.d, self.eclass)["cache"](
            tgtD, khiD, kloD, validD, levD, *cache.dev,
            *self._boundary_scalars(mt, mk, g, P))[:sw.n]
        with self._eval_fetch("eval_cache", sw.n, levD.shape[0]):
            return np.array(need_d)

    def eval_route(self, sw: SweepHandle | None, mt, mk, g: int) -> RoutePairs:
        """Fused boundary routing: compact the (face, element) pairs whose
        neighbor interval reaches outside rank g's partition, with the
        [first, last] owner-rank range per pair.  The host receives ONE
        (count, rows) materialization and builds wire triples from it."""
        if sw is None or sw.n == 0:
            return _empty_route()
        mt = np.asarray(mt, np.int32)
        mk = np.asarray(mk, np.uint64)
        which = self._which(sw.n, "eval_route")
        if which == "reference":
            tgt, nkey, valid, dual, lev = sw.host
            fi, ei = np.nonzero(valid)
            if len(ei) == 0:
                return _empty_route()
            span = _spans_np(self.d, self.ops.L, lev)
            t_v = tgt[fi, ei]
            k_v = nkey[fi, ei]
            first = _owner_np(t_v, k_v, mt, mk)
            last = _owner_np(t_v, k_v + span[ei] - np.uint64(1), mt, mk)
            sel = (first != g) | (last != g)
            return RoutePairs(
                t_v[sel].astype(np.int32), k_v[sel],
                lev[ei[sel]].astype(np.int32), dual[fi, ei][sel].astype(np.int32),
                first[sel], last[sel])
        mt_j, mkey = _padded_markers_cached(mt, mk)
        prog = "route" if which == "jnp" else "route_pallas"
        cnt, packed = _eval_progs(self.d, self.eclass)[prog](
            *sw.dev, mt_j, mkey.hi, mkey.lo, _rank_scalar(g))
        telemetry.count("fetch.eval_route")
        with fetch_span("eval_route"):
            c = int(cnt)
            if c == 0:
                return _empty_route()
            arr = np.asarray(packed[:c])
        khi = np.asarray(arr[:, 1], np.int64) & np.int64(0xFFFFFFFF)
        klo = np.asarray(arr[:, 2], np.int64) & np.int64(0xFFFFFFFF)
        key = (khi.astype(np.uint64) << np.uint64(32)) | klo.astype(np.uint64)
        return RoutePairs(arr[:, 0].copy(), key, arr[:, 3].copy(),
                          arr[:, 4].copy(), arr[:, 5].copy(), arr[:, 6].copy())

    def tree_transform(self, s: Simplex, M, c, typemap) -> Simplex:
        """Cross-tree coordinate change (the `repro.core.cmesh` gluing map):
        anchor' = M @ anchor + c with the reflected-axis anchor correction,
        type through the per-connection `typemap`.  The translation is
        carried modulo 2^32 (see `cmesh.wrap_i32`) so all backends wrap
        identically."""
        from .cmesh import wrap_i32

        M = np.asarray(M, np.int64)
        c32 = wrap_i32(c)
        tm = np.asarray(typemap, np.int64)
        which = self._which(s.level.shape[0], "tree_transform")
        if which == "reference":
            return self.ops.tree_transform(s, M, c32, tm)
        if which == "jnp":
            out, n = self._jnp(
                "tree_transform", s,
                jnp.asarray(M, jnp.int32), jnp.asarray(c32), jnp.asarray(tm, jnp.int32),
            )
            return self._cut(out, n)
        from repro.kernels import ops as kops

        key = (
            tuple(tuple(int(v) for v in row) for row in M.tolist()),
            tuple(int(v) for v in c32.tolist()),
            tuple(int(v) for v in tm.tolist()),
        )
        return self._pallas(kops.tree_transform, s, *key)


@functools.lru_cache(maxsize=None)
def _cached(d: int, backend: str, eclass: int) -> BatchedOps:
    return BatchedOps(d, backend, eclass)


def get_batch_ops(d: int, backend: str | None = None,
                  eclass: int = ECLASS_SIMPLEX) -> BatchedOps:
    """The batched element-ops dispatcher for dimension `d` and element
    class `eclass`.

    With no explicit `backend`, follows the global knob at every call — so
    `use_backend(...)` contexts affect forests that were built earlier.
    """
    return _cached(d, backend if backend is not None else get_backend(), eclass)
