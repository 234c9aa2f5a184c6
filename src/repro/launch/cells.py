"""(architecture x input-shape) cell definitions + lowering.

A *cell* is one entry of the assignment matrix: an ArchConfig plus a
ShapeCell (train_4k / prefill_32k / decode_32k / long_500k).  This module
builds the abstract inputs (ShapeDtypeStructs — no allocation), the
in/out shardings, and the jit-lowered computation for any cell on any mesh.

``long_500k`` is defined only for the sub-quadratic archs (rwkv6-3b,
recurrentgemma-2b); pure full-attention archs skip it (DESIGN.md §5) — a
524288-token dense KV decode is O(S) per token per layer and the assignment
directs the skip.  Encoder-decoder whisper runs decode against its decoder
self-cache + fixed cross-cache.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import LM_ARCHS, get_config
from repro.lm import model as M
from repro.lm.config import ArchConfig, SHAPE_CELLS, ShapeCell
from repro.parallel import sharding as SH
from repro.train import optim as optim_lib

__all__ = ["defined_cells", "cell_matrix", "make_batch_abstract",
           "lower_cell", "model_flops"]


def defined_cells(cfg: ArchConfig) -> Tuple[str, ...]:
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.is_subquadratic or (cfg.window and "attn" not in cfg.layer_types):
        cells.append("long_500k")
    return tuple(cells)


def cell_matrix() -> Tuple[Tuple[str, str], ...]:
    """All defined (arch, cell) pairs of the assignment."""
    out = []
    for arch in LM_ARCHS:
        cfg = get_config(arch)
        out.extend((arch, c) for c in defined_cells(cfg))
    return tuple(out)


def make_batch_abstract(cfg: ArchConfig, cell: ShapeCell) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    B, S = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    if cell.kind == "train" or cell.kind == "prefill":
        if cfg.embedding_inputs:
            batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), dt),
                     "labels": jax.ShapeDtypeStruct((B, S), i32)}
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((B, S + 1), i32)}
        if cfg.encoder_layers:
            batch["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_ctx, cfg.d_model), dt)
        return batch
    # decode: one new token against a cache of length S
    if cfg.embedding_inputs:
        return {"tokens": jax.ShapeDtypeStruct((B, 1, cfg.d_model), dt)}
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}


def model_flops(cfg: ArchConfig, cell: ShapeCell) -> float:
    """MODEL_FLOPS = 6 * N_active * D (tokens processed)."""
    n = cfg.params_active()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        return 2.0 * n * cell.global_batch * cell.seq_len
    return 2.0 * n * cell.global_batch        # decode: one token per seq


def _train_state_abstract(cfg: ArchConfig, opt):
    params = M.abstract_params(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    return {"params": params, "opt": opt_state,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def lower_cell(arch: str, cell_name: str, mesh: Mesh, *,
               quant: str = "none", moe_impl: str = "auto",
               seq_shard: bool = True, remat: bool = True,
               extra_cfg: Optional[dict] = None):
    """Lower one (arch x cell) on a mesh.  Returns (lowered, meta).

    The caller runs ``lowered.compile()`` (launch/dryrun.py) — kept separate
    so compile failures attribute cleanly.
    """
    cfg = get_config(arch)
    cell = SHAPE_CELLS[cell_name]
    over = dict(quant=quant, seq_shard=seq_shard, remat=remat)
    if extra_cfg:
        over.update(extra_cfg)
    if moe_impl != "auto" and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl=moe_impl))
    cfg = dataclasses.replace(cfg, **over)

    batch_abs = make_batch_abstract(cfg, cell)
    bspecs = SH.batch_specs(batch_abs, cfg, mesh, seq_shard=seq_shard)
    params_abs = M.abstract_params(cfg)
    pspecs = SH.param_specs(params_abs, cfg, mesh)
    meta = dict(cfg=cfg, cell=cell,
                model_flops=model_flops(cfg, cell))

    if cell.kind == "train":
        opt = optim_lib.adafactor(1e-3)
        state_abs = _train_state_abstract(cfg, opt)
        sspecs = {"params": pspecs,
                  "opt": SH.opt_state_specs(pspecs, state_abs["opt"], mesh),
                  "step": P()}
        step_fn = M.make_train_step(cfg, mesh, opt)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step_fn,
                in_shardings=(SH.shardings(sspecs, mesh),
                              SH.shardings(bspecs, mesh)),
                out_shardings=(SH.shardings(sspecs, mesh), None),
                donate_argnums=(0,),
            ).lower(state_abs, batch_abs)
        return lowered, meta

    if cell.kind == "prefill":
        fn = functools.partial(M.prefill, cfg=cfg, mesh=mesh,
                               max_len=cell.seq_len)
        cache_abs = M.abstract_cache(cfg, cell.global_batch, cell.seq_len)
        cspecs = SH.cache_specs(cache_abs, cfg, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                lambda params, batch: fn(params, batch),
                in_shardings=(SH.shardings(pspecs, mesh),
                              SH.shardings(bspecs, mesh)),
                out_shardings=(None, SH.shardings(cspecs, mesh)),
            ).lower(params_abs, batch_abs)
        return lowered, meta

    # decode
    dp = tuple(n for n in mesh.axis_names if n in ("pod", "data"))
    cache_abs = M.abstract_cache(cfg, cell.global_batch, cell.seq_len)
    cspecs = SH.cache_specs(cache_abs, cfg, mesh)
    tok_shape = ((cell.global_batch, 1, cfg.d_model) if cfg.embedding_inputs
                 else (cell.global_batch, 1))
    tok_spec = SH.sanitize(
        P(dp, None, None) if cfg.embedding_inputs else P(dp, None),
        tok_shape, mesh)
    fn = functools.partial(M.decode_step, cfg=cfg, mesh=mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            lambda params, caches, tokens, pos: fn(params, caches, tokens, pos),
            in_shardings=(SH.shardings(pspecs, mesh),
                          SH.shardings(cspecs, mesh),
                          NamedSharding(mesh, tok_spec), None),
            out_shardings=(None, SH.shardings(cspecs, mesh)),
            donate_argnums=(1,),
        ).lower(params_abs, cache_abs,
                jax.ShapeDtypeStruct(
                    (cell.global_batch, 1, cfg.d_model) if cfg.embedding_inputs
                    else (cell.global_batch, 1),
                    jnp.dtype(cfg.dtype) if cfg.embedding_inputs else jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, meta
