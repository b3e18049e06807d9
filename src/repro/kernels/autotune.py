"""Kernel-parameter autotuner for the Pallas serving matmuls.

The fused-prologue kernels (``pann_matmul_act`` / ``pann_matmul_packed_act``)
are shape-sensitive in two ways the old one-size heuristic was not: the
persistent VMEM codes panel costs ``bm * K`` bytes (large-K projections want
a smaller bm), and the multi-buffered plane slots cost
``depth * 2 * bk * bn`` (unpacked) or ``depth * 2 * bk * bn / 8`` (packed).
Beyond block shapes, the kernels expose two schedule knobs the tuner
searches: the DMA pipeline depth (VMEM slots per plane stream) and the grid
iteration order ('mnk' row-panel-outer vs 'nmk' N-outer). This module owns

  * the VMEM cost model + deterministic heuristic (``heuristic_blocks`` /
    ``heuristic_params``),
  * a persistent on-disk cache of measured-best parameters keyed by
    ``device_kind | backend | MxKxN | planes | planes_active``
    (``params_for`` / ``record``), and
  * the offline measurement loop (``tune``) that fills it.

``planes_active`` keying: the serving ladder runs EVERY rung through one
compiled kernel (the plane shift is data), so its trace-time lookups key on
the full plane count (active = planes, the default). Offline tuning of a
single-point artifact — where the live plane count is static — may pass
``active`` to record per-count winners; the keys never collide with the
ladder's.

Determinism contract: ``params_for`` is called at TRACE time inside the
jitted decode step, so it must be a pure function of (shape, cache state) —
it never measures, never mutates the cache, and therefore cannot retrace a
warmed engine (``ServeEngine.assert_no_recompile`` holds with the autotuner
active). ``tune`` runs strictly OFFLINE (``ServeEngine(autotune=True)``
before ``warmup``); off-TPU it records the heuristic without timing —
interpret-mode timings are emulator noise, but recording keeps the cache
read/write path exercised by CPU CI.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``.cache/autotune.json`` in the checkout (gitignored), so a run reads no
file from outside its checkout. The file is versioned and rewritten
atomically; a corrupt or foreign-version file is ignored, never crashed on.
Version history: v1 stored bare [bm, bn, bk] triples; v2 adds the schedule
knobs ({"blocks", "depth", "order"}) and the planes_active key segment;
v3 entries hold only whole (32, 128) tiles — a v2 entry may hold a smaller
block, which the TPU compiler refuses.
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Callable, Iterable, NamedTuple, Optional

import jax

from repro import CHECKOUT_DIR

CACHE_VERSION = 3

_ENV_VAR = "REPRO_AUTOTUNE_CACHE"

GRID_ORDERS = ("mnk", "nmk")
DMA_DEPTHS = (2, 3)

# process-local snapshot of the on-disk cache; loaded lazily, kept in sync
# by record(). Maps key -> {"blocks": [bm, bn, bk], "depth": d, "order": o}.
_cache: Optional[dict] = None


class KernelParams(NamedTuple):
    """One tuning decision: block shapes + schedule knobs."""
    bm: int
    bn: int
    bk: int
    depth: int = 2
    order: str = "mnk"

    @property
    def blocks(self) -> tuple[int, int, int]:
        return (self.bm, self.bn, self.bk)


def _as_params(value) -> KernelParams:
    """Normalize a (bm, bn, bk) triple, KernelParams, or cache dict."""
    if isinstance(value, KernelParams):
        return value
    if isinstance(value, dict):
        bm, bn, bk = (int(v) for v in value["blocks"])
        return KernelParams(bm, bn, bk, int(value.get("depth", 2)),
                            str(value.get("order", "mnk")))
    vals = list(value)
    if len(vals) == 3:
        return KernelParams(int(vals[0]), int(vals[1]), int(vals[2]))
    return KernelParams(int(vals[0]), int(vals[1]), int(vals[2]),
                        int(vals[3]), str(vals[4]))


def device_kind() -> str:
    """Autotune cache namespace: the accelerator model ('TPU v5e', ...),
    'cpu' for interpret-mode hosts."""
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "cpu"


def cache_path() -> str:
    env = os.environ.get(_ENV_VAR)
    if env:
        return env
    return os.path.join(CHECKOUT_DIR, ".cache", "autotune.json")


def cache_key(m: int, k: int, n: int, planes: int, backend: str,
              kind: Optional[str] = None,
              active: Optional[int] = None) -> str:
    active = planes if active is None else active
    return (f"{kind or device_kind()}|{backend}|{m}x{k}x{n}"
            f"|p{planes}a{active}")


def _load() -> dict:
    global _cache
    if _cache is None:
        _cache = {}
        try:
            with open(cache_path()) as f:
                data = json.load(f)
            if data.get("version") == CACHE_VERSION:
                _cache = dict(data.get("blocks", {}))
        except (OSError, ValueError):
            pass
    return _cache


def _save() -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    payload = {"version": CACHE_VERSION, "blocks": _load()}
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def clear_memory_cache() -> None:
    """Drop the process-local snapshot (tests; after external file edits)."""
    global _cache
    _cache = None


def vmem_bytes(bm: int, bn: int, bk: int, k: int, packed: bool,
               depth: int = 2) -> int:
    """VMEM working set of the fused-prologue kernels for one grid step."""
    plane_tile = (bk // 8) * bn if packed else bk * bn
    return (4 * bm * bk            # fp32 x landing pad
            + bm * k               # persistent int8 codes panel
            + depth * 2 * plane_tile   # DMA slots x 2 signs
            + 4 * bk * bn          # reconstructed-w int32 scratch
            + 4 * bm * bn          # int32 accumulator
            + 4 * bm * bn)         # f32 output block


# Blocks are whole TPU tiles of the int8 operands: 32 rows of the (bm, K)
# codes panel, 128 lanes along K and N. A smaller M, K or N is padded up to
# the tile by dispatch (zero rows and columns sliced off the result, zero
# weight rows an exact no-op), never given a smaller block, which Mosaic
# refuses.
ROW_TILE = 32
LANE_TILE = 128


def row_block(m: int, cap: int = 128) -> int:
    """The row block for ``m`` rows: ``m`` rounded up to ROW_TILE, at most
    ``cap`` (itself a multiple of ROW_TILE)."""
    return min(cap, -(-m // ROW_TILE) * ROW_TILE)


def lane_block(k: int, cap: int) -> int:
    """The K or N block for extent ``k``: ``k`` rounded up to LANE_TILE, at
    most ``cap`` (itself a multiple of LANE_TILE)."""
    return min(cap, -(-k // LANE_TILE) * LANE_TILE)


def heuristic_blocks(m: int, n: int, k: int, planes: int = 7,
                     packed: bool = False,
                     vmem_budget: int = 8 * 2 ** 20) -> tuple[int, int, int]:
    """Deterministic default: MXU-aligned blocks shrunk until the act-kernel
    working set fits the VMEM budget (bk first — cheapest to shrink — then
    bm, whose cost is dominated by the bm*K codes panel)."""
    bm = row_block(m)
    bn = lane_block(n, 128)
    bk = lane_block(k, 512)
    while bk > LANE_TILE and vmem_bytes(bm, bn, bk, k, packed) > vmem_budget:
        bk = lane_block(bk // 2, 512)
    while bm > ROW_TILE and vmem_bytes(bm, bn, bk, k, packed) > vmem_budget:
        bm = row_block(bm // 2)
    return bm, bn, bk


def heuristic_params(m: int, n: int, k: int, planes: int = 7,
                     packed: bool = False,
                     vmem_budget: int = 8 * 2 ** 20) -> KernelParams:
    """Heuristic blocks + the conservative schedule (double-buffer, 'mnk'
    row-panel-outer — the order whose prologue never re-encodes)."""
    return KernelParams(*heuristic_blocks(m, n, k, planes, packed,
                                          vmem_budget))


def params_for(m: int, k: int, n: int, planes: int, backend: str,
               active: Optional[int] = None) -> KernelParams:
    """Trace-time parameter lookup: measured-best from the cache when
    present, the VMEM heuristic otherwise. Pure in (args, cache state)."""
    hit = _load().get(cache_key(m, k, n, planes, backend, active=active))
    if hit:
        return _as_params(hit)
    return heuristic_params(m, n, k, planes, packed=(backend == "packed"))


def blocks_for(m: int, k: int, n: int, planes: int, backend: str,
               active: Optional[int] = None) -> tuple[int, int, int]:
    """Block-shape view of ``params_for`` (compat shim for callers that
    only consume (bm, bn, bk))."""
    return params_for(m, k, n, planes, backend, active).blocks


def record(m: int, k: int, n: int, planes: int, backend: str,
           params, active: Optional[int] = None) -> None:
    """Persist a tuning decision for ``params_for`` to find. Accepts a
    KernelParams or a bare (bm, bn, bk) triple (depth 2, order 'mnk')."""
    p = _as_params(params)
    _load()[cache_key(m, k, n, planes, backend, active=active)] = {
        "blocks": list(p.blocks), "depth": p.depth, "order": p.order}
    _save()


def candidate_blocks(m: int, n: int, k: int, planes: int,
                     packed: bool = False,
                     vmem_budget: int = 8 * 2 ** 20
                     ) -> list[tuple[int, int, int]]:
    """The block-shape grid: every MXU-aligned (bm, bn, bk) combination
    that fits the VMEM model, heuristic included."""
    bms = sorted({row_block(m, b) for b in (32, 64, 128)})
    bns = sorted({lane_block(n, b) for b in (128, 256)})
    bks = sorted({lane_block(k, b) for b in (128, 256, 512)})
    out = {heuristic_blocks(m, n, k, planes, packed, vmem_budget)}
    for bm in bms:
        for bn in bns:
            for bk in bks:
                if vmem_bytes(bm, bn, bk, k, packed) <= vmem_budget:
                    out.add((bm, bn, bk))
    return sorted(out)


def candidate_params(m: int, n: int, k: int, planes: int,
                     packed: bool = False,
                     vmem_budget: int = 8 * 2 ** 20) -> list[KernelParams]:
    """The full measurement grid: block shapes x DMA depths x grid orders,
    filtered by the depth-aware VMEM model."""
    out = {heuristic_params(m, n, k, planes, packed, vmem_budget)}
    for bm, bn, bk in candidate_blocks(m, n, k, planes, packed, vmem_budget):
        for depth in DMA_DEPTHS:
            if vmem_bytes(bm, bn, bk, k, packed, depth) > vmem_budget:
                continue
            for order in GRID_ORDERS:
                out.add(KernelParams(bm, bn, bk, depth, order))
    return sorted(out)


def tune(m: int, k: int, n: int, planes: int, backend: str,
         runner: Optional[Callable[[KernelParams], float]] = None,
         candidates: Optional[Iterable] = None,
         active: Optional[int] = None) -> KernelParams:
    """Offline: pick the best kernel parameters for one projection shape
    and persist.

    ``runner(params) -> seconds`` measures one candidate (built by
    ``dispatch.tune_projection``). Off-TPU — or with no runner — the
    heuristic is recorded without timing: interpret-mode measurements are
    emulator noise, but the recorded entry still exercises the cache path
    end-to-end in CPU CI. A cached entry short-circuits (idempotent warmup).
    """
    key = cache_key(m, k, n, planes, backend, active=active)
    hit = _load().get(key)
    if hit:
        return _as_params(hit)
    packed = backend == "packed"
    if runner is None or device_kind() == "cpu" or \
            jax.default_backend() != "tpu":
        best = heuristic_params(m, n, k, planes, packed)
    else:
        cands = [_as_params(c) for c in
                 (candidates if candidates is not None
                  else candidate_params(m, n, k, planes, packed))]
        timed = []
        for c in cands:
            try:
                timed.append((runner(c), c))
            except Exception:
                continue        # a candidate the compiler rejects is skipped
        best = min(timed)[1] if timed else \
            heuristic_params(m, n, k, planes, packed)
    record(m, k, n, planes, backend, best, active=active)
    return best
