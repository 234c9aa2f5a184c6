"""repro.api — the one public execution surface.

The paper's accelerator claims to support *emerging neural encodings*
generically; this module makes the claim concrete in software.  The
encoding is a first-class, swappable component
(:class:`~repro.core.encoding.EncodingSpec`: :class:`RadixEncoding`,
:class:`RateEncoding`, :class:`TTFSEncoding`, :class:`PhaseEncoding` —
see ``docs/encodings.md`` for choosing one — or subclass your own), and
execution is one facade::

    from repro import api

    qnet = api.convert(static, params, calib,
                       encoding=api.RadixEncoding(4))     # or num_steps=4
    exe = api.Accelerator(backend="kernels").compile(
        qnet, item_shape, buckets=(1, 8, 32))
    logits = exe(images)                                  # any batch size
    exe.traffic(), exe.memory(), exe.stats()

:class:`Accelerator` owns the *where/how* (backend, in-kernel dataflow);
the spec owns the *what* (quantize/encode/decode/requantize semantics and
which backends/dataflows/pool modes preserve them); ``compile`` validates
the pairing and returns an :class:`Executable` — a batch-polymorphic
callable over a bucketed plan cache (pad-to-bucket, top-bucket chunking,
data-parallel shard_map, zero steady-state recompiles; DESIGN.md §3).

:func:`oracle` is the un-jitted reference forward (``mode="packed"`` or
the paper-faithful ``mode="snn"`` spike-plane path) that every compiled
path is bit-exact against.

Don't want to hand-pick the encoding?  :func:`autoconfigure` searches
the legal (encoding, T, dataflow, units) lattice under accuracy /
latency / energy constraints using the calibrated hardware model
(docs/ppa.md), and ``Accelerator.compile(..., auto=...)`` compiles its
winner directly.  Every compiled executable also reports its modeled
PPA under ``exe.stats()["ppa"]``.

The shipped specs and their level capacity at ``T = 4`` time steps:

>>> from repro import api
>>> [(cls.name, cls(4).levels) for cls in api.SPECS]
[('radix', 16), ('rate', 5), ('ttfs', 16), ('phase', 16)]
>>> api.PhaseEncoding(8, periods=2).levels        # 2 periods x 4 phases
16

This facade subsumes the former ``engine.run(mode=, backend=, method=)``
/ ``engine.compile_plan`` / ``PlanCache`` kwarg sprawl; those survive
only as deprecation shims forwarding here (see DESIGN.md "API" for the
migration table).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import conversion, engine
from repro.core.encoding import (
    SPECS,
    EncodingSpec,
    KernelSchedule,
    PhaseEncoding,
    RadixEncoding,
    RateEncoding,
    TTFSEncoding,
    support_matrix,
    support_matrix_markdown,
)
from repro.core.conversion import convert
from repro.runtime.trace import span

__all__ = [
    "EncodingSpec",
    "KernelSchedule",
    "RadixEncoding",
    "RateEncoding",
    "TTFSEncoding",
    "PhaseEncoding",
    "SPECS",
    "support_matrix",
    "support_matrix_markdown",
    "Accelerator",
    "Executable",
    "LMExecutable",
    "convert",
    "oracle",
    "autoconfigure",
]

BACKENDS = ("kernels", "jnp")


def _is_lm_net(qnet) -> bool:
    """True for the LM compile form: a ``(params, ArchConfig)`` pair
    (``repro.lm``) rather than a converted CNN ``QuantizedNet``."""
    if not (isinstance(qnet, tuple) and len(qnet) == 2):
        return False
    from repro.lm.config import ArchConfig

    return isinstance(qnet[1], ArchConfig)


def _resolve_spec(
    qnet: conversion.QuantizedNet,
    encoding: Optional[EncodingSpec],
) -> EncodingSpec:
    """The spec a net runs under; an explicit override must agree with the
    algebra the net's multipliers were folded for (same levels / steps)."""
    if encoding is None:
        return qnet.spec
    if qnet.encoding is not None and encoding != qnet.encoding:
        raise ValueError(
            f"net was converted for {qnet.encoding}; cannot execute it as "
            f"{encoding} — reconvert with convert(..., encoding=...)")
    if (encoding.num_steps != qnet.num_steps
            or encoding.levels != qnet.spec.levels):
        raise ValueError(
            f"{encoding} ({encoding.levels} levels) does not match the "
            f"net's folded multipliers ({qnet.spec.levels} levels, "
            f"T={qnet.num_steps}) — reconvert with convert(..., "
            f"encoding=...)")
    return encoding


def oracle(
    qnet: conversion.QuantizedNet,
    x,
    *,
    mode: str = "snn",
    encoding: Optional[EncodingSpec] = None,
) -> jax.Array:
    """Un-jitted reference forward on the jnp backend.

    ``mode="snn"`` is the paper-faithful spike-plane path (per-plane
    integer layers, reduced by the encoding's ``reduce_planes``);
    ``mode="packed"`` is the quantized-ANN twin.  Every
    :class:`Executable` is bit-exact against both.

    Args:
        qnet: a converted net (:func:`convert`).
        x: float images, ``(batch,) + item_shape``.
        mode: ``"snn"`` (spike planes) or ``"packed"`` (integer levels).
        encoding: optional spec override; must match the algebra the
            net's multipliers were folded for (normally omit it).

    Returns:
        Float logits, ``(batch, classes)``.

    Raises:
        ValueError: unknown ``mode``, or an ``encoding`` override that
            contradicts the net's stored spec.
    """
    if mode not in ("packed", "snn"):
        raise ValueError(f"mode must be 'packed' or 'snn', got {mode!r}")
    spec = _resolve_spec(qnet, encoding)
    return engine._forward(qnet, jnp.asarray(x, jnp.float32), spec, mode)


def autoconfigure(net, item_shape, *, calib, accuracy_floor,
                  latency_slo_us=None, energy_budget_uj=None, **kwargs):
    """Search the legal (encoding, T, dataflow, units) lattice for a
    float net under PPA constraints; returns a
    :class:`~repro.ppa.search.AutoPlan` (winner + Pareto frontier +
    per-candidate rejection provenance).  Thin facade over
    :func:`repro.ppa.search.autoconfigure` — see docs/ppa.md for the
    walkthrough and constraint semantics.

    Args:
        net: the float ``(static, params)`` pair (conversion format).
        item_shape: per-item input shape, e.g. ``(H, W, C)``.
        calib: calibration batch, ``(n,) + item_shape`` floats.
        accuracy_floor: minimum calibration-batch accuracy (argmax
            fidelity vs the float reference, or label accuracy with
            ``labels=``).
        latency_slo_us: optional modeled per-image latency ceiling.
        energy_budget_uj: optional modeled per-image energy ceiling.
        **kwargs: forwarded to the search (``labels``, ``t_range``,
            ``units``, ``freq_mhz``, ``objective``, ...).
    """
    from repro.ppa import search as ppa_search

    return ppa_search.autoconfigure(
        net, item_shape, calib=calib, accuracy_floor=accuracy_floor,
        latency_slo_us=latency_slo_us, energy_budget_uj=energy_budget_uj,
        **kwargs)


def _attach_ppa(exe: "Executable") -> "Executable":
    """Attach the modeled-PPA stats provider (``stats()["ppa"]``) to a
    freshly compiled executable; nets the hardware model cannot cost
    (exotic layer kinds / item shapes) are skipped silently — stats
    simply lack the key."""
    from repro.ppa import model as ppa_model

    try:
        provider = ppa_model.stats_provider(exe)
    except (ValueError, KeyError, TypeError):
        return exe
    return exe.attach_stats(provider)


def _merge_stat_providers(d: dict, providers) -> dict:
    """Merge attach_stats provider dicts into ``d``; a key that collides
    with an existing one raises instead of silently shadowing it."""
    for provider in providers:
        extra = provider()
        clash = sorted(set(extra) & set(d))
        if clash:
            raise ValueError(
                f"attach_stats provider key(s) {clash} collide with "
                "existing stats keys; namespace provider keys "
                "instead of shadowing core counters")
        d.update(extra)
    return d


class Executable:
    """A compiled, batch-polymorphic deployment of one converted net.

    Produced by :meth:`Accelerator.compile`; do not construct directly.

    ``exe(x)`` maps float images of any batch size to float logits:
    requests pad up to the smallest pre-declared bucket (pad rows sliced
    off) or chunk by the top bucket, so no request size ever recompiles
    on the hot path.  Introspection:

    * :meth:`traffic`  — modeled inter-layer activation bytes (fused
      packed-uint8 plan vs unfused int32 baseline); kernels backend only.
    * :meth:`memory`   — ping-pong buffer sizing / access counts
      (:class:`~repro.core.engine.MemoryReport`).
    * :meth:`stats`    — plan-cache counters (hits / compiles /
      executions / padded_rows / pruned) proving zero steady-state
      recompiles.
    """

    def __init__(
        self,
        qnet: conversion.QuantizedNet,
        item_shape: Tuple[int, ...],
        encoding: EncodingSpec,
        backend: str,
        dataflow: Optional[str],
        parallel: Optional[int],
        buckets: Sequence[int],
        autotune: bool = False,
    ):
        self.qnet = qnet                     # strong ref: exe keeps net alive
        self.item_shape = tuple(int(d) for d in item_shape)
        self.encoding = encoding
        self.backend = backend
        self.dataflow = dataflow
        self.parallel = parallel
        self.autotune = bool(autotune)
        if backend == "kernels":
            self._cache = engine.PlanCache(
                buckets, method=dataflow, data_parallel=parallel,
                encoding=encoding, autotune=autotune)
        else:
            spec = encoding

            def compile_fn(qnet, shape):
                return jax.jit(
                    lambda x: engine._forward(qnet, x, spec, "packed"))

            self._cache = engine.PlanCache(
                buckets, method="jnp", encoding=encoding,
                compile_fn=compile_fn)
        self.buckets = self._cache.buckets
        self._stat_providers: list = []

    def __repr__(self) -> str:
        return (f"Executable({self.encoding}, backend={self.backend!r}, "
                f"dataflow={self.dataflow!r}, item={self.item_shape}, "
                f"buckets={self.buckets})")

    @property
    def num_steps(self) -> int:
        return self.encoding.num_steps

    def __call__(self, x) -> jax.Array:
        """(n,) + item_shape float images -> (n, classes) float logits.

        Any ``n``: pads up to the nearest bucket / chunks by the top
        bucket.  Raises ``ValueError`` when the item shape of ``x`` does
        not match the executable's compiled ``item_shape``.  Host input
        moves to the device here, once, in the ``exe.to_device`` span."""
        shape = np.shape(x)
        if tuple(shape[1:]) != self.item_shape:
            raise ValueError(
                f"request item shape {tuple(shape[1:])} != executable's "
                f"{self.item_shape}")
        with span("exe.to_device", images=shape[0], bytes=4 * math.prod(shape)):
            x = jnp.asarray(x, jnp.float32)
        return self._cache.run(self.qnet, x)

    def warmup(self) -> "Executable":
        """Compile + XLA-warm every bucket so serving never compiles on
        the hot path; returns self for chaining."""
        self._cache.warmup(self.qnet, self.item_shape)
        return self

    def plan_for(self, bucket: int):
        """The underlying per-bucket plan callable (compiles on first
        use) — benchmark hook for timing one bucket without queue/pad
        overhead."""
        return self._cache.plan_for(self.qnet, bucket, self.item_shape)

    def attach_stats(self, provider) -> "Executable":
        """Register an extra stats provider — a zero-arg callable
        returning a dict merged into :meth:`stats` — so layers above the
        executable (e.g. the serving queue's resilience counters,
        DESIGN.md §3) surface through the one stats call.  Providers
        merge in registration order; a provider key that collides with a
        core counter (e.g. ``failures``, ``hits``) or an earlier
        provider's key makes :meth:`stats` raise ``ValueError`` instead
        of silently shadowing the existing value.  Returns self for
        chaining."""
        self._stat_providers.append(provider)
        return self

    def stats(self) -> dict:
        """Plan-cache counters: ``hits`` / ``compiles`` / ``executions``
        / ``padded_rows`` / ``pruned`` / ``failures`` (zero steady-state
        recompiles; ``failures`` counts plan calls that raised — the
        serving queue's recovery path, DESIGN.md §3), plus the
        sparsity-prepass counters ``plane_passes_skipped`` /
        ``plane_passes_total`` (all-zero spike planes the kernel plans
        early-exited or masked, DESIGN.md §8 — zeros on the jnp
        backend, which has no plane schedule to skip), plus an
        ``autotune`` sub-dict — whether compile-time kernel sweeps were
        ``enabled``, the winner-table counters (``hits`` / ``misses`` /
        ``sweeps`` / ``disk_hits``), and one ``layers`` row per
        (bucket, kernel layer) with the strategy each plan baked in
        (docs/kernels.md §7) — plus a ``ppa`` sub-dict with the modeled
        latency/energy/area of this (encoding, dataflow) pairing on the
        calibrated hardware model (docs/ppa.md; absent for nets the
        model cannot cost) — plus any dicts from :meth:`attach_stats`
        providers."""
        from repro.kernels import autotune as autotune_mod

        d = self._cache.stats.as_dict()
        d.update(self._cache.plane_stats())
        d["autotune"] = {
            "enabled": self.autotune,
            **autotune_mod.default_cache().stats.as_dict(),
            "layers": self._cache.tuned_tiles(),
        }
        return _merge_stat_providers(d, self._stat_providers)

    def traffic(self) -> dict:
        """Modeled inter-layer activation bytes, fused packed-uint8 plan
        vs the unfused int32 baseline, for one ``buckets[0]``-sized batch
        (compile with ``buckets=(1, ...)`` for per-item figures; the
        fused/int32 ratio is batch-invariant either way)."""
        if self.backend != "kernels":
            raise NotImplementedError(
                "the activation-traffic model describes compiled kernel "
                "plans; compile with Accelerator(backend='kernels')")
        return self.plan_for(self.buckets[0]).activation_traffic()

    def memory(self, **kwargs) -> engine.MemoryReport:
        """Ping-pong buffer sizing + access counts (paper Sec. III-C)."""
        if len(self.item_shape) != 3:
            raise ValueError(
                "memory() models (H, W, C) image nets, item_shape="
                f"{self.item_shape}")
        return engine.memory_report(self.qnet, self.item_shape, **kwargs)


class LMExecutable:
    """A compiled autoregressive LM serving deployment (beyond-paper).

    Produced by :meth:`Accelerator.compile` when handed an
    ``(params, ArchConfig)`` pair instead of a converted CNN; do not
    construct directly.  The transformer's FFN / unembed matmuls (and the
    QKV/out projections under ``cfg.radix_attn``) run as radix matmuls —
    through the autotuned kernel stack on ``backend="kernels"``, through
    the fused int8 ``dot_general`` twin on ``backend="jnp"`` — and the KV
    cache is the packed radix inter-step activation format
    (``repro.lm.radix``; docs/lm.md is the guide).

    Serving shape contract (an :class:`~repro.core.engine.LMPlanCache`):
    prompts right-pad to a fixed **sequence-bucket ladder** (one jitted
    prefill plan per bucket, last-token logits gathered at the true
    length) and every generated token reuses ONE jitted decode-step plan
    over the radix KV cache — zero steady-state recompiles, asserted via
    :meth:`stats` exactly like the CNN path.  Exactness of the
    right-padding trick needs a pure full-attention stack (the causal
    mask hides pad positions), so other block types are rejected at
    compile time.
    """

    def __init__(self, params, cfg, *, batch: int, max_len: int,
                 seq_buckets: Sequence[int], backend: str,
                 dataflow: Optional[str], autotune: bool):
        from repro.lm import model as lm_model

        bad = sorted(set(cfg.layer_types) - {"attn"})
        if bad:
            raise ValueError(
                "the LM compile path right-pads prompts to sequence "
                "buckets, which is exact only for pure full-attention "
                f"stacks (causal masking hides the pads); block types "
                f"{bad} would absorb pad tokens into recurrent/ring state "
                "— serve those archs via repro.launch.serve.generate")
        if cfg.encoder_layers or cfg.embedding_inputs:
            raise ValueError(
                "the LM compile path serves token-in/token-out decoder "
                "stacks; encoder-decoder and embedding-input archs run "
                "via repro.launch.serve.generate")
        serve_cfg = dataclasses.replace(
            cfg, quant="radix",
            use_kernel=(backend == "kernels"),
            kernel_autotune=bool(autotune),
            kernel_dataflow=dataflow or cfg.kernel_dataflow)
        self.cfg = serve_cfg
        self.arch = cfg.name
        self.backend = backend
        self.dataflow = serve_cfg.kernel_dataflow if backend == "kernels" \
            else None
        self.autotune = bool(autotune)
        self.batch = int(batch)
        self.max_len = int(max_len)
        if self.batch < 1 or self.max_len < 2:
            raise ValueError(
                f"need batch >= 1 and max_len >= 2, got ({batch}, {max_len})")
        self.params = lm_model.radixify_params(params, serve_cfg)
        self._model = lm_model

        mdl, mx, scfg = lm_model, self.max_len, serve_cfg

        def prefill_builder(bucket):
            def fn(p, tokens, true_len):
                return mdl.prefill(p, {"tokens": tokens}, scfg, None,
                                   max_len=mx, true_len=true_len)
            return jax.jit(fn)

        def decode_builder():
            def fn(p, caches, tok, pos):
                return mdl.decode_step(p, caches, tok, pos, scfg, None)
            return jax.jit(fn)

        self._cache = engine.LMPlanCache(
            seq_buckets, prefill_builder=prefill_builder,
            decode_builder=decode_builder)
        self.buckets = self._cache.buckets
        if self.buckets[-1] >= self.max_len:
            raise ValueError(
                f"top sequence bucket {self.buckets[-1]} must stay below "
                f"max_len={self.max_len} (the KV cache needs at least one "
                "free decode slot)")
        self._tuned_rows: list = []
        if self.autotune:
            self._tuned_rows = self._sweep()
        self._stat_providers: list = []

    def __repr__(self) -> str:
        return (f"LMExecutable({self.arch!r}, T={self.cfg.radix_steps}, "
                f"backend={self.backend!r}, dataflow={self.dataflow!r}, "
                f"batch={self.batch}, max_len={self.max_len}, "
                f"seq_buckets={self.buckets})")

    @property
    def num_steps(self) -> int:
        return self.cfg.radix_steps

    def _sweep(self) -> list:
        """Eagerly autotune every radix matmul problem the compiled plans
        will trace — prefill runs each weight at ``m = batch * bucket``
        rows, decode and the lm-head at ``m = batch`` — so the
        Tracer-safe winner lookup inside jit (ops._resolve_config) always
        hits and plans bake the swept strategy in."""
        import numpy as np

        from repro.core import encoding as encoding_mod
        from repro.kernels import autotune as autotune_mod, ops as kops

        problems: list = []

        def walk(t, path=""):
            if isinstance(t, dict):
                if set(t) == {"q", "scale"}:
                    q = t["q"]
                    q2 = q.reshape((-1,) + q.shape[-2:])[0] if q.ndim > 2 \
                        else q
                    problems.append((path, q2))
                    return
                for k in sorted(t):
                    walk(t[k], f"{path}/{k}" if path else k)
            elif isinstance(t, (tuple, list)):
                for i, v in enumerate(t):
                    walk(v, f"{path}/{i}")

        walk(self.params)
        T = self.cfg.radix_steps
        lvl = encoding_mod.max_level(T)
        method = self.cfg.kernel_dataflow
        rng = np.random.default_rng(0)
        rows, seen = [], set()
        for name, q2 in problems:
            k, n = int(q2.shape[0]), int(q2.shape[1])
            head = name.endswith("unembed")
            ms = {self.batch} if head else (
                {self.batch * b for b in self.buckets} | {self.batch})
            for m in sorted(ms):
                key = autotune_mod.matmul_key(
                    m, k, n, T, method, epilogue=False, sparsity=False)
                if key in seen:
                    continue
                seen.add(key)
                x = jnp.asarray(
                    rng.integers(0, lvl + 1, size=(m, k)), jnp.uint8)
                jax.block_until_ready(kops.radix_matmul(
                    x, q2, None, T, method=method, autotune=True))
                win = autotune_mod.default_cache().get(key)
                rows.append({
                    "layer": name, "m": m, "k": k, "n": n,
                    "tuned": win is not None,
                    **(win or autotune_mod.KernelConfig()).as_dict()})
        if self.cfg.packed_attn and self.cfg.radix_kv:
            rows.extend(self._sweep_attn(rng))
        return rows

    def _sweep_attn(self, rng) -> list:
        """Autotune the packed decode-attention problem the decode plan
        traces (kernels/radix_attn.py): one problem at S = max_len over
        a synthetic radix cache, so the KV-block winner is baked in."""
        from repro.kernels import autotune as autotune_mod, ops as kops
        from repro.lm import radix as radix_lib

        cfg, B, S = self.cfg, self.batch, self.max_len
        T, hkv, hd = cfg.radix_steps, cfg.n_kv_heads, cfg.hd
        g = cfg.n_heads // hkv
        lvl = (1 << T) - 1
        packed = radix_lib._packed(cfg)
        method = cfg.kernel_dataflow
        key = autotune_mod.attn_key(
            B, S, hkv, g, hd, T, method,
            q_bits=kops.Q_BITS, packed=packed, sparsity=True)
        q = jnp.asarray(rng.normal(size=(B, hkv * g, hd)), jnp.float32)
        k_q = rng.integers(0, lvl + 1, size=(B, S, hkv, hd)).astype("uint8")
        v_q = rng.integers(0, lvl + 1, size=(B, S, hkv, hd)).astype("uint8")
        if packed:
            k_q = (k_q[..., 0::2] << 4) | k_q[..., 1::2]
            v_q = (v_q[..., 0::2] << 4) | v_q[..., 1::2]
        scale = jnp.ones((B, S, hkv), jnp.float32)
        mask = jnp.ones((B, S), bool)
        jax.block_until_ready(kops.radix_decode_attention(
            q, jnp.asarray(k_q), scale, jnp.asarray(v_q), scale, mask, T,
            packed=packed, method=method, autotune=True))
        win = autotune_mod.default_cache().get(key)
        return [{
            "layer": "decode_attn", "m": B, "k": hd, "n": S,
            "tuned": win is not None,
            **(win or autotune_mod.KernelConfig()).as_dict()}]

    def prefill(self, prompts) -> dict:
        """Prefill ``prompts`` ((n, S0) int tokens, n <= batch) through
        the bucketed plan; returns the serving state dict
        ``{"caches", "pos", "logits", "n"}`` — ``logits`` (n, vocab)
        predict the token at position S0."""
        prompts = jnp.asarray(prompts, jnp.int32)
        if prompts.ndim != 2:
            raise ValueError(f"prompts must be (n, S0), got {prompts.shape}")
        n, s0 = int(prompts.shape[0]), int(prompts.shape[1])
        if n > self.batch:
            raise ValueError(
                f"request batch {n} exceeds compiled batch {self.batch}")
        bucket = self._cache.bucket_for(s0)
        # +1 column: model._input_h consumes tokens[:, :-1]
        tokens = jnp.pad(prompts,
                         ((0, self.batch - n), (0, bucket - s0 + 1)))
        plan = self._cache.prefill_plan(bucket)
        logits, caches = plan(self.params, tokens, jnp.int32(s0))
        self._cache.record_execution(
            padded_rows=(self.batch - n) + (bucket - s0))
        return {"caches": caches, "pos": s0, "logits": logits[:n], "n": n}

    def decode(self, state: dict, tokens) -> dict:
        """One decode step: write ``tokens`` ((n, 1) int) at
        ``state["pos"]``, return the advanced state (``logits`` predict
        position pos + 1)."""
        n = state["n"]
        pos = int(state["pos"])
        if pos >= self.max_len:
            raise ValueError(
                f"decode position {pos} out of cache range "
                f"(max_len={self.max_len})")
        tok = jnp.asarray(tokens, jnp.int32).reshape(n, 1)
        tok = jnp.pad(tok, ((0, self.batch - n), (0, 0)))
        plan = self._cache.decode_plan()
        logits, caches = plan(self.params, state["caches"], tok,
                              jnp.int32(pos))
        self._cache.record_execution(padded_rows=self.batch - n)
        return {"caches": caches, "pos": pos + 1, "logits": logits[:n],
                "n": n}

    def generate(self, prompts, max_new: int, *, greedy: bool = True,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Autoregressive decode: (n, S0) prompts -> (n, max_new) tokens
        (greedy argmax, or categorical samples with ``key``)."""
        prompts = jnp.asarray(prompts, jnp.int32)
        s0 = int(prompts.shape[1])
        if s0 + max_new - 1 > self.max_len:
            raise ValueError(
                f"prompt ({s0}) + max_new ({max_new}) tokens exceed the "
                f"compiled cache (max_len={self.max_len})")
        state = self.prefill(prompts)
        out = []
        for i in range(int(max_new)):
            if greedy:
                nxt = jnp.argmax(state["logits"], axis=-1).astype(jnp.int32)
            else:
                if key is None:
                    raise ValueError("sampling (greedy=False) needs key=")
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, state["logits"].astype(jnp.float32)).astype(jnp.int32)
            out.append(nxt)
            if i + 1 < max_new:
                state = self.decode(state, nxt[:, None])
        return jnp.stack(out, axis=1)

    def warmup(self) -> "LMExecutable":
        """Build + execute every prefill bucket plan and the decode-step
        plan so serving never compiles on the hot path; returns self."""
        caches = None
        for b in self.buckets:
            tokens = jnp.zeros((self.batch, b + 1), jnp.int32)
            plan = self._cache.prefill_plan(b)
            logits, caches = plan(self.params, tokens, jnp.int32(b))
            jax.block_until_ready(logits)
        dplan = self._cache.decode_plan()
        tok = jnp.zeros((self.batch, 1), jnp.int32)
        logits, _ = dplan(self.params, caches, tok,
                          jnp.int32(self.buckets[-1]))
        jax.block_until_ready(logits)
        return self

    def attach_stats(self, provider) -> "LMExecutable":
        """Register an extra stats provider (same contract as
        :meth:`Executable.attach_stats`); returns self for chaining."""
        self._stat_providers.append(provider)
        return self

    def stats(self) -> dict:
        """LM plan-cache counters (``hits`` / ``compiles`` /
        ``executions`` / ``padded_rows`` / ``failures`` — ``compiles``
        stays flat in steady state: one prefill plan per sequence bucket
        plus one decode plan), plus the ``autotune`` sub-dict — whether
        the eager sweep ran (``enabled``), the winner-table counters, and
        one ``layers`` row per swept (layer, m, k, n) problem with the
        strategy the plans bake in — plus any dicts from
        :meth:`attach_stats` providers."""
        from repro.kernels import autotune as autotune_mod

        d = self._cache.stats.as_dict()
        d["autotune"] = {
            "enabled": self.autotune,
            **autotune_mod.default_cache().stats.as_dict(),
            "layers": list(self._tuned_rows),
        }
        return _merge_stat_providers(d, self._stat_providers)


@dataclasses.dataclass(frozen=True)
class Accelerator:
    """The execution target: which backend runs plans, with which
    in-kernel dataflow.

    * ``backend="kernels"`` — fused-epilogue Pallas kernel plans
      (interpret-mode on CPU, compiled on TPU); ``dataflow`` picks the
      in-kernel schedule among the encoding's declared
      ``kernel_dataflows`` (radix: "fused" default, "bitserial" for the
      paper-faithful schedule).  The kernels execute the encoding's
      declared :class:`KernelSchedule` (docs/kernels.md) and always run
      the plane-occupancy sparsity prepass — all-zero spike planes are
      skipped (bitserial) or masked (fused), bit-exactly, with skip
      counts in :meth:`Executable.stats`.
    * ``backend="jnp"``     — per-bucket jitted XLA closures of the
      reference path; the only backend for encodings without a kernel
      dataflow (e.g. :class:`RateEncoding`).

    ``compile`` validates the (backend, dataflow, encoding, net) pairing
    loudly at compile time — no silent fall-through to a slower or
    semantically wrong path.

    >>> from repro import api
    >>> api.Accelerator(backend="jnp").backend
    'jnp'
    >>> api.Accelerator(dataflow="bitserial").dataflow
    'bitserial'
    """

    backend: str = "kernels"
    dataflow: Optional[str] = None   # None -> encoding's default

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}")
        if self.dataflow is not None and self.backend != "kernels":
            raise ValueError(
                f"dataflow={self.dataflow!r} selects the in-kernel "
                "schedule and requires backend='kernels'")

    def compile(
        self,
        qnet: conversion.QuantizedNet,
        input_spec: Sequence[int],
        *,
        encoding: Optional[EncodingSpec] = None,
        parallel: Optional[int] = None,
        buckets: Optional[Sequence[int]] = None,
        autotune: bool = False,
        auto: Optional[dict] = None,
    ) -> Executable:
        """Compile ``qnet`` for deployment; returns an :class:`Executable`.

        ``input_spec`` is the per-item input shape — ``(H, W, C)`` for
        image nets — batch handling is the executable's job.  ``buckets``
        is the pre-compiled batch ladder (default
        ``engine.DEFAULT_BUCKETS``); ``parallel`` shards each bucket's
        plan over up to that many devices (None = auto,
        gcd(bucket, devices)).  ``encoding`` overrides the net's stored
        spec (it must match the folded multiplier algebra — normally you
        pass the encoding to :func:`convert` once and never here).

        ``autotune=True`` (kernels backend only) times the legal kernel
        strategies per layer at plan-compile time — Pallas tile shapes,
        MXU dot lowerings proven bit-exact by
        :func:`repro.kernels.autotune.exact_lowering`, the
        plane-parallel grid, and the jitted XLA twin — and bakes each
        winner into the plan.  Winners persist in a process + on-disk
        table (``$REPRO_AUTOTUNE_CACHE``), so only the first compile of
        a problem shape pays the sweep; results are bit-identical either
        way.  Inspect the choices via ``Executable.stats()["autotune"]``.

        ``auto=`` hands configuration to the PPA planner: pass a dict of
        :func:`autoconfigure` keywords (``calib`` + ``accuracy_floor``
        required) and a *float* ``(static, params)`` pair as the first
        argument instead of a converted net — the planner searches the
        encoding/T/dataflow/units lattice, and the winner is converted
        and compiled (its backend/dataflow supersede this accelerator's;
        the plan is exposed as ``exe.auto_plan``).  See docs/ppa.md.

        Raises:
            ValueError: the encoding does not run on this backend (see
                the support matrix in ``docs/encodings.md``), the
                dataflow is not among the encoding's declared
                ``kernel_dataflows``, a pool mode in the net is not
                preserved by the encoding, ``parallel`` is requested off
                the kernels backend, or an ``encoding`` override
                contradicts the net's folded multipliers; with
                ``auto=``, an explicit ``dataflow``/``encoding`` (the
                planner owns those axes) or a search that satisfies no
                constraint.
        """
        if _is_lm_net(qnet):
            return self._compile_lm(qnet, input_spec, encoding=encoding,
                                    parallel=parallel, buckets=buckets,
                                    autotune=autotune, auto=auto)
        if auto is not None:
            if self.dataflow is not None:
                raise ValueError(
                    "auto= searches the dataflow axis; leave "
                    "Accelerator.dataflow=None")
            if encoding is not None:
                raise ValueError(
                    "auto= searches the encoding axis; drop the "
                    "encoding= override")
            from repro.ppa import search as ppa_search

            plan = ppa_search.autoconfigure(qnet, input_spec, **dict(auto))
            exe = plan.compile(parallel=parallel, buckets=buckets,
                               autotune=autotune)
            exe.auto_plan = plan
            return exe
        spec = _resolve_spec(qnet, encoding)
        if self.backend not in spec.backends:
            raise ValueError(
                f"{spec.name} encoding does not run on the "
                f"{self.backend!r} backend (supported: {spec.backends})")
        dataflow = None
        if self.backend == "kernels":
            dataflow = spec.validate_dataflow(self.dataflow)
        else:
            if parallel is not None and parallel != 1:
                raise ValueError(
                    "parallel (data-parallel bucket plans) requires "
                    "backend='kernels'")
            if autotune:
                raise ValueError(
                    "autotune sweeps kernel strategies and requires "
                    "backend='kernels'")
        spec.validate_static(qnet.static)
        item = tuple(int(d) for d in input_spec)
        if buckets is None:
            buckets = engine.DEFAULT_BUCKETS
        return _attach_ppa(Executable(qnet, item, spec, self.backend,
                                      dataflow, parallel, buckets,
                                      autotune=autotune))

    def _compile_lm(self, qnet, input_spec, *, encoding, parallel, buckets,
                    autotune, auto) -> LMExecutable:
        """The LM leg of :meth:`compile` — ``qnet`` is ``(params, cfg)``
        with ``cfg`` an :class:`~repro.lm.config.ArchConfig`.

        ``input_spec`` is ``(max_len,)`` or ``(batch, max_len)`` — the
        compiled decode batch and the KV-cache capacity.  ``buckets`` is
        the **sequence-length** ladder (prompts pad to the smallest
        bucket; default: powers of two from 8 up to ``max_len - 1``);
        every bucket must stay below ``max_len`` so decode has cache
        room.  The paper-technique knobs live on the ArchConfig itself
        (``radix_steps`` = T, ``radix_kv`` / ``radix_kv_pack``,
        ``radix_attn``); docs/lm.md is the guide.
        """
        params, cfg = qnet
        if auto is not None:
            raise ValueError(
                "auto= (the PPA planner) prices the paper's CNN lattice, "
                "not LM archs; configure the ArchConfig directly")
        if encoding is not None:
            raise ValueError(
                "LM serving always runs the radix encoding "
                "(cfg.radix_steps sets T); drop the encoding= override")
        if parallel not in (None, 1):
            raise ValueError(
                "parallel bucket sharding is a CNN-plan feature; LM "
                "plans shard via the model's mesh instead")
        if autotune and self.backend != "kernels":
            raise ValueError(
                "autotune sweeps kernel strategies and requires "
                "backend='kernels'")
        if self.dataflow is not None and self.dataflow not in (
                "bitserial", "fused"):
            raise ValueError(
                f"LM radix matmuls support dataflow 'bitserial' or "
                f"'fused', got {self.dataflow!r}")
        spec = tuple(int(d) for d in input_spec)
        if len(spec) == 1:
            batch, max_len = 1, spec[0]
        elif len(spec) == 2:
            batch, max_len = spec
        else:
            raise ValueError(
                f"LM input_spec is (max_len,) or (batch, max_len), "
                f"got {input_spec}")
        if buckets is None:
            top = max(1, max_len - 1)
            ladder = {top}
            b = 8
            while b < top:
                ladder.add(b)
                b *= 2
            buckets = tuple(sorted(ladder))
        return LMExecutable(params, cfg, batch=batch, max_len=max_len,
                            seq_buckets=buckets, backend=self.backend,
                            dataflow=self.dataflow, autotune=autotune)
