"""Block-size / lowering autotuner for the radix kernels.

The paper's premise is that packed low-bit spike planes *beat* dense
arithmetic — but only if the plane passes run on the hardware's native
MAC datapath (E3NE schedules bit-plane passes onto DSP slices for the
same reason).  Which execution strategy is native differs per backend:

* **TPU** — the Pallas kernels with ``mxu_dtype="int8"`` (int8 operands,
  ``preferred_element_type=int32``): one MXU pass per plane at the int8
  systolic rate, tile shapes sized to VMEM.  This is also the untuned
  default: the TPU compiler refuses an int32 x int32 dot, so no Pallas
  kernel emits one.
* **CPU CI** — Pallas runs in interpret mode, and XLA:CPU has no VNNI /
  AMX matmul lowering (integer ``dot_general`` falls back to scalar
  loops, ~6x slower than the BLAS float path).  Here the winner is the
  ``impl="xla"`` twin with ``mxu_dtype="f32"``: the *same* plane-pass
  math, but each dot runs as an f32 GEMM — **bit-exact** as long as any
  partial sum fits the f32 mantissa (the :func:`exact_lowering` guard).

Nobody should hand-pick among those per (shape, T, dataflow, schedule):
:func:`tune` times every legal :class:`KernelConfig` candidate with the
caller-supplied builder and caches the winner in a process-level table
and an on-disk JSON table (``REPRO_AUTOTUNE_CACHE``), consulted by
``ops.radix_matmul`` / ``ops.radix_conv2d`` / plan compilation
(``engine._compile_plan_impl(..., autotune=True)`` →
``Accelerator.compile(..., autotune=True)``).

Everything here is deliberately pure data + timing: candidate
generation, exactness guards, cache keys, and winner selection.  The
strategy *builders* (what a config executes) live in ``ops.py`` so this
module never imports the kernels and cannot create an import cycle.

Determinism: winners are selected by ``min(time, candidate order)`` —
with the injectable ``timer`` two equal timings resolve to the earlier
candidate, so tests (and re-sweeps over a stable candidate list) are
reproducible.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "ACT_DTYPES",
    "MXU_DTYPES",
    "KernelConfig",
    "AutotuneCache",
    "exact_lowering",
    "matmul_key",
    "conv_key",
    "attn_key",
    "matmul_candidates",
    "conv_candidates",
    "attn_candidates",
    "tune",
    "default_cache",
    "cache_path",
]

MXU_DTYPES = ("int8", "f32")
ACT_DTYPES = ("u8", "f32")       # activation layout at the layer boundary
_F32_MANTISSA = 1 << 24          # f32 sums of integers are exact below this
_WEIGHT_MAX = 127                # int8 weight magnitude bound


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One executable strategy for a radix matmul / conv layer.

    ``impl="pallas"`` runs the Pallas tile program (compiled on TPU,
    interpret-mode on CPU) with grid tiles ``(bm, bk, bn)`` / ``bco``;
    ``impl="xla"`` runs the jitted XLA twin of the same plane-pass math
    (no tiling — XLA picks its own blocking).  ``mxu_dtype`` selects the
    per-plane ``dot_general`` lowering: ``"int8"`` (operands cast to
    int8, ``preferred_element_type=int32`` — the TPU MXU-native path and
    the default; always exact, see :func:`exact_lowering`) or ``"f32"``
    (BLAS-rate float dots, exact under :func:`exact_lowering`).
    ``plane_parallel`` moves the bitserial plane loop into its own grid
    dimension under weight-stationary block specs (Pallas only): the
    weight tile's index map is independent of the plane index, so one
    weight load serves all ``T x periods`` plane passes.

    ``act_dtype`` declares the **activation memory layout** the strategy
    wants at the layer boundary: ``"u8"`` is the packed-level contract
    (1 byte/element — what compiled plans ship between layers; the HBM
    win the paper's output logic buys), ``"f32"`` holds the same exact
    integer levels in the f32 GEMM's native operand layout, trading 4x
    activation bytes for a zero-convert dot (the right trade on CPU,
    where the only fast GEMM is f32 and the convert is pure overhead;
    on TPU the packed layout feeds the int8 MXU directly and wins both).
    Callers that own the layer boundary (standalone ``ops`` calls, the
    bench) honor it by presenting the input in the declared layout;
    compiled plans pin the packed inter-layer contract and sweep with
    ``act_dtypes=("u8",)``.  Only offered on the fused XLA twin, where
    no bit algebra needs an integer view of the operand.
    """

    impl: str = "pallas"              # "pallas" | "xla"
    mxu_dtype: str = "int8"           # per-plane dot lowering
    bm: int = 128                     # matmul M tile (pallas)
    bk: int = 128                     # matmul K tile (pallas)
    bn: int = 128                     # matmul N tile (pallas)
    bco: int = 128                    # conv out-channel tile (pallas)
    plane_parallel: bool = False      # bitserial plane-grid dimension
    act_dtype: str = "u8"             # activation layout at the boundary

    def __post_init__(self):
        if self.impl not in ("pallas", "xla"):
            raise ValueError(f"impl must be 'pallas' or 'xla', {self.impl!r}")
        if self.mxu_dtype not in MXU_DTYPES:
            raise ValueError(
                f"mxu_dtype must be one of {MXU_DTYPES}, {self.mxu_dtype!r}")
        if self.act_dtype not in ACT_DTYPES:
            raise ValueError(
                f"act_dtype must be one of {ACT_DTYPES}, {self.act_dtype!r}")
        if self.act_dtype == "f32" and self.mxu_dtype != "f32":
            raise ValueError(
                "act_dtype='f32' requires mxu_dtype='f32': the f32 "
                "boundary layout exists to feed the f32 GEMM directly")

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        return cls(**d)


# ---------------------------------------------------------------------------
# Exactness guard: when is a lowering bit-exact?
# ---------------------------------------------------------------------------


def exact_lowering(
    mxu_dtype: str,
    *,
    max_operand: int,
    k_contract: int,
    method: str,
) -> bool:
    """True iff ``mxu_dtype`` reproduces the integer accumulation exactly.

    ``max_operand`` is the largest activation value a dot can see
    (``2^T - 1`` for the fused packed pass, 1 for a bitserial plane
    pass), ``k_contract`` the total contraction length of one layer
    (``K`` for matmuls, ``kh * kw * Cin`` for convs).

    * ``int8`` — always exact: int8 x int8 -> int32 MXU passes.  Weights
      and plane bits fit int8 as they are; packed levels fit while
      ``T <= 7``, and a wider operand (``T >= 8``, a sum-pool carry) is
      split into 7-bit slices, one pass each, shifted back in int32
      (``radix_matmul.int8_contract``).  It is the default lowering.
    * ``f32``  — products and partial sums are integers computed in
      f32; exact while every partial sum stays below the 24-bit
      mantissa.  One headroom bit is reserved for the epilogue bias add.
    """
    if mxu_dtype == "int8":
        return True
    operand = 1 if method == "bitserial" else max_operand
    if mxu_dtype == "f32":
        return operand * _WEIGHT_MAX * k_contract <= _F32_MANTISSA // 2
    raise ValueError(mxu_dtype)


# ---------------------------------------------------------------------------
# Cache keys — one winner per (problem, schedule, dataflow, backend).
# ---------------------------------------------------------------------------


def _schedule_fields(schedule) -> Tuple[int, int, str]:
    """(packed_bits, periods, out_grid) of a KernelSchedule or bare T."""
    if hasattr(schedule, "packed_bits"):
        return (int(schedule.packed_bits), int(schedule.periods),
                str(schedule.out_grid))
    return (int(schedule), 1, "dense")


def matmul_key(
    m: int, k: int, n: int, schedule, dataflow: str,
    *, epilogue: bool, sparsity: bool, backend: Optional[str] = None,
) -> tuple:
    """Tuning-table key for a matmul problem.

    The key includes the full encoding schedule (packed bits, periods,
    output grid) AND the dataflow — radix T=4 and phase T=8/P=2 pack
    the same 4 bits per byte but replay different plane schedules, and a
    winner tuned for ``fused`` says nothing about ``bitserial``; folding
    any of those into one slot would be the same aliasing bug the plan
    cache once had with recycled ``id()`` keys.
    """
    bits, periods, grid = _schedule_fields(schedule)
    backend = backend or jax.default_backend()
    return ("matmul", backend, int(m), int(k), int(n), bits, periods,
            grid if epilogue else "raw", str(dataflow), bool(epilogue),
            bool(sparsity))


def conv_key(
    h: int, w: int, cin: int, kh: int, kw: int, cout: int, stride: int,
    schedule, dataflow: str,
    *, batch: int, epilogue: bool, sparsity: bool,
    backend: Optional[str] = None,
) -> tuple:
    """Tuning-table key for a conv problem (same aliasing rules)."""
    bits, periods, grid = _schedule_fields(schedule)
    backend = backend or jax.default_backend()
    return ("conv", backend, int(batch), int(h), int(w), int(cin), int(kh),
            int(kw), int(cout), int(stride), bits, periods,
            grid if epilogue else "raw", str(dataflow), bool(epilogue),
            bool(sparsity))


def attn_key(
    batch: int, s_len: int, hkv: int, g: int, hd: int, num_steps: int,
    dataflow: str, *, q_bits: int, packed: bool, sparsity: bool,
    backend: Optional[str] = None,
) -> tuple:
    """Tuning-table key for one packed decode-attention problem.

    Lives in the same winner table as the matmul/conv keys (the "attn"
    tag disambiguates).  ``packed`` (nibble-packed cache) changes the
    in-kernel unpack and therefore which tile shapes win, so it is part
    of the key; the mask content (full vs ring-buffer window) is not —
    strategy legality and cost depend only on the shapes."""
    backend = backend or jax.default_backend()
    return ("attn", backend, int(batch), int(s_len), int(hkv), int(g),
            int(hd), int(num_steps), int(q_bits), str(dataflow),
            bool(packed), bool(sparsity))


# ---------------------------------------------------------------------------
# Candidate generation.
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_options(dim: int, pref: int = 128, align: int = 8) -> List[int]:
    """Tile sizes to sweep for one dimension: the ops.py heuristic
    (128-aligned, or the whole dim rounded to 8 when small) plus the
    full-dimension single block (grid-loop-free — what wins in
    interpret mode) and a half split for VMEM pressure."""
    if dim < pref:
        return [_round_up(dim, align)]
    full = _round_up(dim, align)
    opts = [pref, full]
    half = _round_up(full // 2, align)
    if half >= pref and half not in opts:
        opts.append(half)
    return sorted(set(opts))


def _dtype_options(schedule, method: str, k_contract: int) -> List[str]:
    bits, _, _ = _schedule_fields(schedule)
    max_operand = (1 << bits) - 1
    return [d for d in MXU_DTYPES
            if exact_lowering(d, max_operand=max_operand,
                              k_contract=k_contract, method=method)]


def matmul_candidates(
    m: int, k: int, n: int, schedule, dataflow: str,
    *, interpret: bool, act_dtypes: Sequence[str] = ACT_DTYPES,
) -> List[KernelConfig]:
    """Legal strategies for one matmul problem, heuristic-first.

    The first candidate is always the untuned default (Pallas, int8
    lowering, heuristic 128 tiles) so an interrupted or budget-capped
    sweep can never regress below the untuned path.  On the interpret
    backend (CPU) the sweep leans on the XLA twin + full-dim tiles —
    grid steps are Python-loop overhead there; on compiled backends it
    sweeps MXU tile shapes.  ``act_dtypes`` is the activation-layout
    space the caller can serve: compiled plans pass ``("u8",)`` (the
    packed inter-layer contract); callers that own the layer boundary
    leave the default and the sweep may also offer the f32-layout fused
    twin (exact — the same ``exact_lowering`` guard gates it).
    """
    dtypes = _dtype_options(schedule, dataflow, k)
    cands: List[KernelConfig] = [KernelConfig()]     # the untuned default
    for dt in dtypes:
        cands.append(KernelConfig(impl="xla", mxu_dtype=dt))
    if "f32" in act_dtypes and "f32" in dtypes and dataflow == "fused":
        cands.append(KernelConfig(impl="xla", mxu_dtype="f32",
                                  act_dtype="f32"))
    for dt in dtypes:
        for bm in _tile_options(m):
            for bk in _tile_options(k):
                for bn in _tile_options(n):
                    cands.append(KernelConfig(
                        impl="pallas", mxu_dtype=dt, bm=bm, bk=bk, bn=bn))
                    if dataflow == "bitserial":
                        cands.append(KernelConfig(
                            impl="pallas", mxu_dtype=dt, bm=bm, bk=bk,
                            bn=bn, plane_parallel=True))
    if interpret:
        # interpret-mode Pallas is a validation vehicle, not a perf one:
        # sweep only the single-block tile so the sweep stays cheap.
        cands = [c for c in cands
                 if c.impl == "xla"
                 or (c.bm, c.bk, c.bn) == (128, 128, 128)
                 or (c.bm >= m and c.bk >= k and c.bn >= n)]
    return _dedup(cands)


def conv_candidates(
    h: int, w: int, cin: int, kh: int, kw: int, cout: int, schedule,
    dataflow: str, *, interpret: bool,
    act_dtypes: Sequence[str] = ACT_DTYPES,
) -> List[KernelConfig]:
    """Legal strategies for one conv problem (see matmul_candidates)."""
    dtypes = _dtype_options(schedule, dataflow, kh * kw * cin)
    cands: List[KernelConfig] = [KernelConfig()]
    for dt in dtypes:
        cands.append(KernelConfig(impl="xla", mxu_dtype=dt))
    if "f32" in act_dtypes and "f32" in dtypes and dataflow == "fused":
        cands.append(KernelConfig(impl="xla", mxu_dtype="f32",
                                  act_dtype="f32"))
    for dt in dtypes:
        for bco in _tile_options(cout):
            cands.append(KernelConfig(impl="pallas", mxu_dtype=dt, bco=bco))
            if dataflow == "bitserial":
                cands.append(KernelConfig(
                    impl="pallas", mxu_dtype=dt, bco=bco,
                    plane_parallel=True))
    if interpret:
        cands = [c for c in cands
                 if c.impl == "xla" or c.bco in (128, _round_up(cout, 8))]
    return _dedup(cands)


def _attn_dtype_options(num_steps: int, q_bits: int, hd: int,
                        dataflow: str) -> List[str]:
    """Exact lowerings for the attention QK^T integer dot.

    Both operands are activations here (query levels <= 2^q_bits - 1,
    key levels <= 2^T - 1 fused / plane bits bitserial), so the gate runs
    on the larger of the two — int8 is always exact (the kernel slices
    an operand wider than int8), and the f32 mantissa bound stays
    conservative (the 127 weight factor dominates the true smaller
    operand)."""
    qlvl = (1 << q_bits) - 1
    lvl = (1 << num_steps) - 1
    operand = qlvl if dataflow == "bitserial" else max(qlvl, lvl)
    return [d for d in MXU_DTYPES
            if exact_lowering(d, max_operand=operand, k_contract=hd,
                              method="fused")]


def attn_candidates(
    s_len: int, hd: int, num_steps: int, dataflow: str,
    *, q_bits: int, interpret: bool,
) -> List[KernelConfig]:
    """Legal strategies for one decode-attention problem.

    ``bk`` is repurposed as the KV-block (sequence) tile of the streaming
    online softmax — the block-size sweep the tentpole asks for.  The
    first candidate is always the untuned default; the XLA twin sweeps a
    full-cache single block (one dot, what wins on CPU) alongside the
    default blocked loop.  Integer-dot lowerings pass the same
    ``exact_lowering`` gate as the matmul kernels; the float
    softmax/value part reassociates across block sizes, so candidates
    agree to f32 rounding rather than bit-for-bit (the differential
    suite pins all of them to the ref.py oracle)."""
    dtypes = _attn_dtype_options(num_steps, q_bits, hd, dataflow)
    full = _round_up(s_len, 8)
    cands: List[KernelConfig] = [KernelConfig()]     # the untuned default
    for dt in dtypes:
        cands.append(KernelConfig(impl="xla", mxu_dtype=dt))
        if full != 128:
            cands.append(KernelConfig(impl="xla", mxu_dtype=dt, bk=full))
    for dt in dtypes:
        for bk in _tile_options(s_len):
            cands.append(KernelConfig(impl="pallas", mxu_dtype=dt, bk=bk))
    if interpret:
        # interpret-mode Pallas is a validation vehicle: single block only
        cands = [c for c in cands
                 if c.impl == "xla" or c.bk in (128, full) or c.bk >= s_len]
    return _dedup(cands)


def _dedup(cands: Sequence[KernelConfig]) -> List[KernelConfig]:
    seen, out = set(), []
    for c in cands:
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


# ---------------------------------------------------------------------------
# The cache: process-level dict + on-disk JSON table.
# ---------------------------------------------------------------------------


def cache_path() -> Optional[pathlib.Path]:
    """On-disk table location: ``$REPRO_AUTOTUNE_CACHE`` (empty string
    disables persistence), else ``~/.cache/repro/autotune.json``."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env is not None:
        return pathlib.Path(env) if env else None
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


def _key_str(key: tuple) -> str:
    return "|".join(str(part) for part in key)


@dataclasses.dataclass
class AutotuneStats:
    """Counters proving steady state never re-sweeps."""

    hits: int = 0         # winner served from the process table
    misses: int = 0       # key not in the process table
    sweeps: int = 0       # full candidate sweeps actually timed
    disk_hits: int = 0    # misses resolved from the on-disk table
    skipped: int = 0      # candidates whose build or run raised
    first_error: Optional[str] = None   # the first such error, verbatim

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class AutotuneCache:
    """Winner table: process-level dict backed by an on-disk JSON file.

    Lookups hit the in-memory table first, then the disk table (loaded
    lazily once), then report a miss; :meth:`put` writes through to disk
    (best-effort — an unwritable path degrades to process-level only).
    Thread-safe: the serving stack compiles plans from worker threads.
    """

    def __init__(self, path: Optional[os.PathLike] = None):
        self.path = pathlib.Path(path) if path is not None else None
        self.stats = AutotuneStats()
        self._mem: dict = {}
        self._disk_loaded = False
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._mem)

    def _load_disk(self) -> None:
        if self._disk_loaded:
            return
        self._disk_loaded = True
        if self.path is None or not self.path.exists():
            return
        try:
            payload = json.loads(self.path.read_text())
            for ks, entry in payload.get("entries", {}).items():
                self._mem.setdefault(
                    ks, (KernelConfig.from_dict(entry["config"]),
                         float(entry.get("us", 0.0))))
        except (OSError, ValueError, TypeError, KeyError):
            pass                      # a corrupt table is just a cold cache

    def get(self, key: tuple) -> Optional[KernelConfig]:
        ks = _key_str(key)
        with self._lock:
            hit = self._mem.get(ks)
            if hit is not None:
                self.stats.hits += 1
                return hit[0]
            before = len(self._mem)
            self._load_disk()
            hit = self._mem.get(ks)
            if hit is not None:
                self.stats.disk_hits += 1
                self.stats.hits += 1
                return hit[0]
            del before
            self.stats.misses += 1
            return None

    def put(self, key: tuple, config: KernelConfig, us: float) -> None:
        ks = _key_str(key)
        with self._lock:
            self._load_disk()
            self._mem[ks] = (config, float(us))
            self._flush()

    def _flush(self) -> None:
        if self.path is None:
            return
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            payload = {
                "version": 1,
                "entries": {
                    ks: {"config": cfg.as_dict(), "us": us}
                    for ks, (cfg, us) in sorted(self._mem.items())
                },
            }
            self.path.write_text(json.dumps(payload, indent=1) + "\n")
        except OSError:
            pass                      # read-only FS -> process-level cache


_DEFAULT_CACHE: Optional[AutotuneCache] = None
_DEFAULT_LOCK = threading.Lock()


def default_cache() -> AutotuneCache:
    """The process-wide winner table (created on first use)."""
    global _DEFAULT_CACHE
    with _DEFAULT_LOCK:
        if _DEFAULT_CACHE is None:
            _DEFAULT_CACHE = AutotuneCache(cache_path())
        return _DEFAULT_CACHE


def reset_default_cache() -> None:
    """Drop the process-wide table (tests; also picks up a changed
    ``REPRO_AUTOTUNE_CACHE``)."""
    global _DEFAULT_CACHE
    with _DEFAULT_LOCK:
        _DEFAULT_CACHE = None


# ---------------------------------------------------------------------------
# Timing + winner selection.
# ---------------------------------------------------------------------------


def measure(fn: Callable[[], object], *, iters: int = 5,
            warmup: int = 1) -> float:
    """Min-of-N wall clock of ``fn()`` in microseconds (blocks on the
    result).  Min — not mean — because scheduling noise only ever adds
    time; the minimum is the closest observable to the true cost."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def tune(
    key: tuple,
    candidates: Sequence[KernelConfig],
    build: Callable[[KernelConfig], Callable[[], object]],
    *,
    cache: Optional[AutotuneCache] = None,
    timer: Optional[Callable[[Callable[[], object]], float]] = None,
    iters: int = 5,
) -> KernelConfig:
    """The tuning loop: consult the cache, else time every candidate.

    ``build(config)`` returns a zero-arg thunk executing the strategy on
    representative inputs; ``timer`` (injectable — tests pass a fake)
    maps a thunk to microseconds, defaulting to :func:`measure`.  A
    candidate whose build or execution raises (e.g. a tile shape or a
    lowering the backend rejects) is skipped, but never silently: it
    counts in ``cache.stats.skipped`` and the first error is kept in
    ``cache.stats.first_error``.  The winner is the minimum time with
    ties broken by candidate order, which makes selection deterministic
    under any injected timer.  The winner is cached (process + disk).
    """
    cache = cache if cache is not None else default_cache()
    hit = cache.get(key)
    if hit is not None:
        return hit
    if not candidates:
        raise ValueError("no candidates to tune over")
    timer = timer if timer is not None else (
        lambda fn: measure(fn, iters=iters))
    best: Optional[Tuple[float, int, KernelConfig]] = None
    for idx, cand in enumerate(candidates):
        try:
            thunk = build(cand)
            us = float(timer(thunk))
        except Exception as err:      # illegal strategy for this problem
            cache.stats.skipped += 1
            if cache.stats.first_error is None:
                cache.stats.first_error = (
                    f"{cand}: {type(err).__name__}: {err}")
            continue
        if best is None or (us, idx) < (best[0], best[1]):
            best = (us, idx, cand)
    cache.stats.sweeps += 1
    if best is None:
        raise RuntimeError(
            f"autotune: every candidate failed for key {key}; first "
            f"error: {cache.stats.first_error}")
    cache.put(key, best[2], best[0])
    return best[2]
