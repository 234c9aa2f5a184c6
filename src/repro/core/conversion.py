"""ANN -> SNN conversion (the paper's model-preparation path, ref [14] E3NE).

Pipeline:
  1. train a float ANN (see train/),
  2. calibrate per-layer activation scales on a calibration batch,
  3. quantize weights to ``weight_bits`` (paper: 3) symmetric signed integers,
  4. fold scales into per-layer requantization multipliers.

The result is a :class:`QuantizedNet` whose spiking and packed-integer
execution paths are bit-exact twins (see core/layers.py).

Model description format
------------------------
A network is ``(static, params)``:

* ``static``: tuple of ``(kind, cfg)`` pairs; ``kind`` in
  {"conv", "linear", "pool", "flatten"}; cfg is a dict of ints/strings
  (stride, padding, window, mode).  A conv's ``padding`` is "SAME",
  "VALID" or explicit ``((lo, hi), (lo, hi))``; a pool takes an optional
  ``stride`` (default: the window) and ``padding``.  A converted net may
  also hold ``"block"`` entries, basic residual blocks with ``{"stride":
  s, "proj": bool}`` (DESIGN.md §2); :func:`convert` does not produce
  them (a float residual net's conversion is not implemented).
* ``params``: list with one entry per layer; {"w": ..., "b": ...} for
  conv/linear, ``None`` for pool/flatten.

The last conv/linear layer produces float logits (no requantization).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import encoding, layers
from repro.core.encoding import EncodingSpec, RadixEncoding

__all__ = [
    "float_forward",
    "calibrate",
    "quantize_weights",
    "convert",
    "QuantizedNet",
]

Static = Tuple[Tuple[str, dict], ...]


# ---------------------------------------------------------------------------
# Float reference network (training target).
# ---------------------------------------------------------------------------


def float_forward(
    static: Static,
    params: Sequence[Optional[dict]],
    x: jax.Array,
    *,
    return_activations: bool = False,
):
    """Float ANN forward.  ReLU after every conv/linear except the last.

    Pool mode "avg"/"max"/"or" — "or" trains as max (its straight-through
    float surrogate).
    """
    acts = []
    n_affine = sum(1 for k, _ in static if k in ("conv", "linear"))
    seen_affine = 0
    for (kind, cfg), p in zip(static, params):
        if kind == "conv":
            seen_affine += 1
            x = jax.lax.conv_general_dilated(
                x, p["w"],
                window_strides=(cfg.get("stride", 1),) * 2,
                padding=cfg.get("padding", "VALID"),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p["b"]
            if seen_affine < n_affine:
                x = jax.nn.relu(x)
                acts.append(x)
        elif kind == "linear":
            seen_affine += 1
            x = x @ p["w"] + p["b"]
            if seen_affine < n_affine:
                x = jax.nn.relu(x)
                acts.append(x)
        elif kind == "pool":
            mode = cfg.get("mode", "or")
            if mode == "avg":
                x = jax.lax.reduce_window(
                    x, 0.0, jax.lax.add,
                    (1, cfg["window"], cfg["window"], 1),
                    (1, cfg["window"], cfg["window"], 1), "VALID",
                ) / float(cfg["window"] ** 2)
            else:  # max / or share the max float surrogate
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max,
                    (1, cfg["window"], cfg["window"], 1),
                    (1, cfg["window"], cfg["window"], 1), "VALID",
                )
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    if return_activations:
        return x, acts
    return x


# ---------------------------------------------------------------------------
# Calibration + weight quantization.
# ---------------------------------------------------------------------------


def calibrate(
    static: Static, params, calib_x: jax.Array, percentile: float = 99.9
) -> List[float]:
    """Per-requant-point activation scales (max or high percentile).

    Returns one scale per conv/linear layer *input*: scales[0] is the input
    scale (images assumed in [0, 1] -> 1.0 unless data says otherwise),
    scales[i] is the scale of the activation feeding affine layer i.
    """
    _, acts = float_forward(static, params, calib_x, return_activations=True)
    # activation scale after each ReLU / pool — we only need those feeding
    # affine layers; conservative approach: track the running scale.
    scales = [float(max(1.0, jnp.max(calib_x)))]
    for a in acts:
        if percentile >= 100.0:
            s = float(jnp.max(a))
        else:
            s = float(jnp.percentile(a, percentile))
        scales.append(max(s, 1e-6))
    return scales


def quantize_weights(w: jax.Array, weight_bits: int,
                     per_channel: bool = False):
    """Symmetric quantization to ``weight_bits`` signed levels.

    3 bits (paper) -> levels in [-3, 3] (symmetric, zero preserved).
    ``per_channel=True`` uses one scale per output channel (the last dim);
    the extra scales fold into the per-channel requantization multiplier in
    the output logic — same 3-bit weight memory, much lower quantization
    error (DESIGN.md §2 assumption notes).
    """
    qmax = 2 ** (weight_bits - 1) - 1
    if per_channel:
        s_w = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1))) / qmax
        s_w = jnp.maximum(s_w, 1e-12)
    else:
        s_w = max(float(jnp.max(jnp.abs(w))) / qmax if qmax > 0 else 1.0,
                  1e-12)
    w_q = jnp.clip(jnp.round(w / s_w), -qmax, qmax).astype(jnp.int8)
    return w_q, s_w


# ---------------------------------------------------------------------------
# The converted network.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(eq=False)
class QuantizedNet:
    """Converted network: integer weights + folded requant multipliers.

    qlayers mirrors ``static``; each entry is a dict:
      conv/linear: {"w_q", "b_int", "mult"(None for logits layer)}
      block: {"a": {"w_q", "b_int", "mult"}, "b": {"w_q", "b_int",
              "mult"}, "proj": {"w_q", "b_int"} or None,
              "res_scale": int32 (C_out,)} — 3x3/s conv_a, 3x3/1 conv_b,
              the optional 1x1/s projection and the join's integer
              factor (DESIGN.md §2)
      pool/flatten: None
    ``logit_scale`` maps the last integer accumulator to float logits.

    ``encoding`` is the :class:`~repro.core.encoding.EncodingSpec` the
    multipliers were folded for (``None`` on nets converted before specs
    existed — read :attr:`spec`, which defaults those to radix).  Identity
    semantics (``eq=False``) keep the net hashable so weakrefs to it can
    key the engine's plan caches.
    """

    static: Static = dataclasses.field(metadata=dict(static=True))
    num_steps: int = dataclasses.field(metadata=dict(static=True))
    weight_bits: int = dataclasses.field(metadata=dict(static=True))
    qlayers: List[Optional[dict]] = dataclasses.field(default_factory=list)
    input_scale: float = 1.0
    logit_scale: float = 1.0
    encoding: Optional[encoding.EncodingSpec] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def spec(self) -> encoding.EncodingSpec:
        """The net's encoding spec (legacy nets default to radix)."""
        if self.encoding is not None:
            return self.encoding
        return encoding.RadixEncoding(self.num_steps)


def convert(
    static: Static,
    params,
    calib_x: jax.Array,
    *,
    num_steps: Optional[int] = None,
    encoding: Optional[EncodingSpec] = None,
    weight_bits: int = 3,
    percentile: float = 99.9,
    per_channel: bool = False,
) -> QuantizedNet:
    """ANN -> SNN conversion (scales folded; see module docstring).

    The target encoding is a first-class parameter: pass ``encoding``
    (e.g. ``RadixEncoding(4)``, ``RateEncoding(7)``, ``TTFSEncoding(4)``,
    ``PhaseEncoding(8, periods=2)`` — docs/encodings.md has the selection
    guide) or, as shorthand for radix, just ``num_steps``.  The spec's
    ``levels`` drives the multiplier folding (radix: 2^T; rate: T+1;
    TTFS: 2^T grid units; phase: 2^(T/P)) and the spec is stored on the
    returned net, so execution paths dispatch on it without re-stating
    the encoding at every call site (repro.api).

    Raises:
        ValueError: neither ``num_steps`` nor ``encoding`` given, a
            contradictory (``num_steps``, ``encoding``) pair, or a pool
            mode in ``static`` the encoding does not preserve.
    """
    spec = encoding
    if spec is None:
        if num_steps is None:
            raise ValueError("pass num_steps (radix shorthand) or encoding")
        spec = RadixEncoding(num_steps)
    elif num_steps is not None and num_steps != spec.num_steps:
        raise ValueError(
            f"num_steps={num_steps} contradicts "
            f"encoding.num_steps={spec.num_steps}")
    spec.validate_static(static)
    scales = calibrate(static, params, calib_x, percentile)
    # fold the spec's headroom factor into every calibrated scale, so the
    # quantize / bias / multiplier / logit algebra below stays consistent
    # with the grid the spec actually quantizes onto.
    scales = [s * spec.scale_factor for s in scales]
    lvlp1 = spec.levels  # radix: 2^T levels; rate: T+1

    qlayers: List[Optional[dict]] = []
    affine_idx = 0
    n_affine = sum(1 for k, _ in static if k in ("conv", "linear"))
    s_in = scales[0]
    input_scale = s_in
    pending_pool_div = 1.0  # avg-pool window division folded into next requant
    logit_scale = 1.0
    for (kind, cfg), p in zip(static, params):
        if kind in ("conv", "linear"):
            affine_idx += 1
            w_q, s_w = quantize_weights(p["w"], weight_bits, per_channel)
            # accumulator unit value: (s_in / 2^T) * s_w / pending_pool_div
            # (a per-output-channel vector when per_channel)
            acc_unit = (s_in / lvlp1) * s_w / pending_pool_div
            b_int = jnp.round(p["b"] / acc_unit).astype(jnp.int32)
            if affine_idx < n_affine:
                s_out = scales[affine_idx]
                mult = jnp.asarray(acc_unit * lvlp1 / s_out, jnp.float32)
                qlayers.append({"w_q": w_q, "b_int": b_int, "mult": mult})
                s_in = s_out
            else:
                logit_scale = acc_unit
                qlayers.append({"w_q": w_q, "b_int": b_int, "mult": None})
            pending_pool_div = 1.0
        elif kind == "pool":
            mode = cfg.get("mode", "or")
            if mode == "avg":
                # sum-pool accumulates; fold the 1/window^2 into next requant
                pending_pool_div = float(cfg["window"] ** 2)
            # max/or pools preserve levels; scale after pool uses the
            # calibrated post-pool scale only through the float surrogate —
            # keep s_in unchanged (levels unchanged).
            qlayers.append(None)
        elif kind == "flatten":
            qlayers.append(None)
        else:
            raise ValueError(kind)

    return QuantizedNet(
        static=static,
        num_steps=spec.num_steps,
        weight_bits=weight_bits,
        encoding=spec,
        qlayers=qlayers,
        input_scale=float(input_scale),
        logit_scale=(float(logit_scale) if jnp.ndim(logit_scale) == 0
                     else jnp.asarray(logit_scale, jnp.float32)),
    )
