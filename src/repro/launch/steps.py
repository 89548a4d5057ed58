"""Step builders for the dry-run / roofline: per (arch × shape) jit-able
train/prefill/decode step functions with abstract inputs, shardings, and
cost units.

Cost units (DESIGN.md §8): ``cost_analysis()`` counts a ``lax.scan`` body
once, so each bundle carries per-layer body functions + trip multipliers.
Units are lowered with a *cost-variant* config (attn_chunk=0, single SSM
chunk) whose FLOPs equal the chunked production variant, avoiding nested
scan corrections.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ARCH_IDS, SHAPES, ModelConfig, ShapeConfig,
                                load_config, shape_cells)
from repro.models import layers as Lyr
from repro.models import mamba as M
from repro.models import model as Mdl
from repro.models.sharding import ax, axis_size
from repro.train import optimizer as Opt

SDS = jax.ShapeDtypeStruct
I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16


@dataclass
class CostUnit:
    name: str
    multiplier: int
    fn: Callable
    abstract_args: tuple
    in_shardings: Any


@dataclass
class StepBundle:
    arch: str
    shape: str
    kind: str
    fn: Callable
    abstract_args: tuple
    in_shardings: Any
    out_shardings: Any
    donate_argnums: tuple
    cost_units: list
    model_flops: float
    notes: str = ""


# ---------------------------------------------------------------------------
# Per-cell policy: memory levers chosen so each cell fits 16 GB/chip v5e
# ---------------------------------------------------------------------------

def plan_rules(arch: str, shape_name: str) -> dict:
    """Pick the parallelism scheme per cell (call under the mesh context).

    Pure-DP+FSDP (batch over data×model, NO tensor axis) beats SP/TP for
    token-heavy steps whenever the batch divides the mesh and the per-layer
    gathered weight slab stays small: zero per-layer activation collectives,
    only bf16 weight all-gathers (§Perf iteration 4). Falls back to the
    SP/TP scheme (DEFAULT_RULES) otherwise — e.g. grok (9.7 GB expert slab)
    and prefill_32k (batch 32 < data×model).
    """
    from repro.models.sharding import axis_size
    shape = SHAPES[shape_name] if shape_name in SHAPES else None
    if shape is None or arch.startswith("svfusion"):
        return {}
    rules: dict = {}
    if shape.global_batch % max(axis_size("batch"), 1) != 0:
        rules["batch"] = ()          # e.g. long_500k batch=1
        return rules
    cfg = load_config(arch)
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    data_model = sizes.get("data", 1) * sizes.get("model", 1)
    layer_slab_gb = count_params(cfg) / max(cfg.n_layers, 1) * 2 / 1e9
    dims_ok = (cfg.d_model % data_model == 0
               and (cfg.d_ff == 0 or cfg.d_ff % data_model == 0))
    if (shape.kind == "train" and shape.global_batch % data_model == 0
            and layer_slab_gb < 2.0 and "pod" not in sizes and dims_ok):
        rules["batch"] = ("data", "model")
        rules["fsdp"] = ("data", "model")
        rules["tensor"] = ()
    return rules


def tune_config(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    kw: dict = {}
    if shape.kind in ("train", "prefill"):
        kw["gather_weights"] = True   # token-heavy: gather weights, don't
        # partial-sum over the fsdp-sharded contraction (§Perf)
        if shape.seq_len >= 8192 or (shape.kind == "train"
                                     and cfg.d_model >= 4096):
            kw["attn_chunk"] = 2048
        # residual-stream sharding when per-device layer carries get big
        est = (shape.global_batch / 32) * shape.seq_len * cfg.d_model * 2 \
            * max(cfg.n_layers, 1)
        if shape.kind == "train" and est > 3e9:
            kw["residual_shard"] = "dmodel" if cfg.family in ("ssm", "hybrid") \
                else "seq"
        if shape.kind == "prefill":
            kw["remat_policy"] = "none"       # inference: no backward
            if cfg.family in ("ssm", "hybrid"):
                kw["residual_shard"] = "dmodel"
            elif shape.seq_len * cfg.d_model * 2 > 5e7:
                kw["residual_shard"] = "seq"
    if shape.kind == "decode":
        kw["remat_policy"] = "none"
        kw["moe_group"] = 1
    return cfg.replace(**kw) if kw else cfg


def cost_variant(cfg: ModelConfig, seq_len: int) -> ModelConfig:
    return cfg.replace(attn_chunk=0, ssm_chunk=max(seq_len, 1))


def moe_flops_factor(cfg) -> float:
    """Active fraction of MLP params per token (MoE top-k vs dense)."""
    if cfg.n_experts:
        return cfg.top_k  # d_ff is per-expert; top_k experts active
    return 1.0


def count_params(cfg: ModelConfig) -> float:
    """Analytical parameter count (excluding embeddings for 6ND)."""
    D, F, Dh = cfg.d_model, cfg.d_ff, cfg.head_dim
    attn = D * (cfg.n_heads + 2 * cfg.n_kv_heads) * Dh \
        + cfg.n_heads * Dh * D
    mlp = 3 * D * F * (cfg.n_experts or 1)
    ssm = 0
    if cfg.family in ("ssm", "hybrid"):
        Din = cfg.d_inner
        R = cfg.dt_rank_eff
        ssm = D * 2 * Din + cfg.d_conv * Din + Din * (R + 2 * cfg.d_state) \
            + R * Din + Din * cfg.d_state + Din * D
    if cfg.family == "ssm":
        per_layer = ssm
    elif cfg.family == "hybrid":
        per_layer = attn + ssm + 3 * D * F
    elif cfg.family == "encdec":
        per_layer = 0  # computed separately below
    else:
        per_layer = attn + 3 * D * F * (cfg.n_experts or 1)
    if cfg.family == "encdec":
        enc = (attn + 3 * D * F) * cfg.n_enc_layers
        dec = (2 * attn + 3 * D * F) * cfg.n_dec_layers
        return enc + dec
    return per_layer * cfg.n_layers


def active_params(cfg: ModelConfig) -> float:
    """N_active for MODEL_FLOPS = 6·N_active·D (MoE counts top_k experts)."""
    D, F = cfg.d_model, cfg.d_ff
    total = count_params(cfg)
    if cfg.n_experts:
        total -= 3 * D * F * cfg.n_experts * cfg.n_layers
        total += 3 * D * F * cfg.top_k * cfg.n_layers
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N_active·tokens for train; 2·N_active·tokens for forward-only
    (plus attention quadratic term)."""
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    n_act = active_params(cfg)
    mult = 6.0 if shape.kind == "train" else 2.0
    if cfg.family == "encdec" and shape.kind != "train":
        # encoder sees seq_len frames; decoder only its own token budget
        D, F, Dh = cfg.d_model, cfg.d_ff, cfg.head_dim
        attn_p = D * (cfg.n_heads + 2 * cfg.n_kv_heads) * Dh \
            + cfg.n_heads * Dh * D
        enc_p = (attn_p + 3 * D * F) * cfg.n_enc_layers
        dec_p = (2 * attn_p + 3 * D * F) * cfg.n_dec_layers
        tok_enc = shape.global_batch * shape.seq_len
        tok_dec = shape.global_batch * (min(shape.seq_len, 4096)
                                        if shape.kind == "prefill" else 1)
        flops = mult * (enc_p * tok_enc + dec_p * tok_dec)
        if shape.kind == "prefill":
            tokens = tok_enc  # attention term below keyed to encoder side
    else:
        flops = mult * n_act * tokens
    # attention score/O term
    if cfg.n_heads:
        Dh, Hq = cfg.head_dim, cfg.n_heads
        if shape.kind == "decode":
            kv = shape.seq_len
            att = 4.0 * shape.global_batch * Hq * Dh * kv
            if cfg.family == "hybrid":
                att *= 3.0 / cfg.n_layers  # only global layers see full kv
                att += 4.0 * shape.global_batch * Hq * Dh \
                    * min(cfg.swa_window, kv) * (cfg.n_layers - 3) / cfg.n_layers
            att *= cfg.n_layers if cfg.family != "encdec" else cfg.n_dec_layers * 2
        else:
            att = (mult / 6 * 12.0 if shape.kind == "train" else 4.0) \
                * tokens * shape.seq_len * Hq * Dh / 2
            att *= cfg.n_layers if cfg.family != "encdec" \
                else (cfg.n_enc_layers + 2 * cfg.n_dec_layers)
            if cfg.family == "hybrid":
                w = min(cfg.swa_window, shape.seq_len)
                full = tokens * shape.seq_len / 2
                swa = tokens * w
                att = att / cfg.n_layers * (3 * 1.0 + (cfg.n_layers - 3)
                                            * (swa / full))
        flops += att
    return flops


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def batch_inputs(cfg: ModelConfig, shape: ShapeConfig, with_labels=True):
    B, S = shape.global_batch, shape.seq_len
    bspec = ax("batch", None)
    if cfg.family == "vlm":
        S_tok = S - cfg.n_patches
        abs_in = {"tokens": SDS((B, S_tok), I32),
                  "patches": SDS((B, cfg.n_patches, cfg.d_model), BF16)}
        specs = {"tokens": bspec, "patches": ax("batch", None, None)}
        if with_labels:
            abs_in["labels"] = SDS((B, S_tok), I32)
            specs["labels"] = bspec
    elif cfg.family == "encdec":
        abs_in = {"frames": SDS((B, S, cfg.d_model), BF16),
                  "tokens": SDS((B, S if shape.kind == "train" else
                                 min(S, 4096)), I32)}
        specs = {"frames": ax("batch", None, None), "tokens": bspec}
        if with_labels:
            abs_in["labels"] = SDS(abs_in["tokens"].shape, I32)
            specs["labels"] = bspec
    else:
        abs_in = {"tokens": SDS((B, S), I32)}
        specs = {"tokens": bspec}
        if with_labels:
            abs_in["labels"] = SDS((B, S), I32)
            specs["labels"] = bspec
    return abs_in, specs


def abstract_cache(cfg: ModelConfig, shape: ShapeConfig):
    B = shape.global_batch
    cache = jax.eval_shape(lambda: Mdl.init_cache(cfg, B, shape.seq_len))
    specs = Mdl.cache_specs(cfg, long_context=(shape.name == "long_500k"))
    return cache, specs


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, adam: Opt.AdamConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: Mdl.loss_fn(cfg, p, batch))(params)
        params, opt_state, metrics = Opt.adam_update(params, grads,
                                                     opt_state, adam)
        return params, opt_state, loss
    return train_step


def _train_unit(cfg_cost, layer_fwd):
    """vjp of one remat'd layer body — forward + recompute + backward."""
    def unit(p_layer, x):
        f = Lyr.maybe_remat(lambda pp, xx: layer_fwd(pp, xx),
                            cfg_cost.remat_policy)
        y, vjp = jax.vjp(f, p_layer, x)
        return vjp(jnp.ones_like(y))
    return unit


def _layer_template_and_specs(cfg, fam_key):
    tpl = Mdl.build_templates(cfg)
    if fam_key in ("layers", "enc", "dec"):
        sub = tpl[fam_key]
    else:  # hybrid groups
        sub = tpl[fam_key]
    # strip the stacked leading dim
    def strip(t):
        return Lyr.TSpec(t.shape[1:], t.axes[1:], t.scale)
    sub1 = jax.tree.map(strip, sub, is_leaf=lambda x: isinstance(x, Lyr.TSpec))
    return (Lyr.abstract_from_template(sub1, jnp.dtype(cfg.param_dtype)),
            Lyr.specs_from_template(sub1))


def make_cost_units(cfg: ModelConfig, shape: ShapeConfig) -> list:
    """Per-layer bodies + multipliers for scan-count correction."""
    cfgc = cost_variant(cfg, shape.seq_len)
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    x_abs = SDS((B, S if kind != "decode" else 1, cfg.d_model), BF16)
    x_spec = ax("batch", None, None)
    units = []
    pos = jnp.arange(S)

    def add(name, mult, fn, args, shardings):
        if mult > 0:
            units.append(CostUnit(name, mult, fn, args, shardings))

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        p_abs, p_spec = _layer_template_and_specs(cfg, "layers")
        if kind == "train":
            fwd = lambda pp, xx: Mdl.dense_layer_fwd(cfgc, pp, xx, pos)[0]
            add("layer", cfg.n_layers - 1, _train_unit(cfgc, fwd),
                (p_abs, x_abs), (p_spec, x_spec))
        elif kind == "prefill":
            fn = lambda pp, xx: Mdl.dense_layer_fwd(cfgc, pp, xx, pos)[0]
            add("layer", cfg.n_layers - 1, fn, (p_abs, x_abs),
                (p_spec, x_spec))
        else:  # decode
            cache, cspecs = abstract_cache(cfg, shape)
            kc = SDS(cache["k"].shape[1:], cache["k"].dtype)
            vc = SDS(cache["v"].shape[1:], cache["v"].dtype)
            kspec = P(*cspecs["k"][1:])

            def dec_fn(pp, xx, kc, vc):
                posn = jnp.asarray(S - 1, I32)
                h = Lyr.rms_norm(xx, pp["ln1"], cfgc.norm_eps)
                o, kc, vc, _ = Mdl._decode_attn_layer(cfgc, pp, h, kc, vc,
                                                      posn, posn + 1)
                xx = xx + Lyr.attn_out(pp["attn"], o, cfgc)
                h = Lyr.rms_norm(xx, pp["ln2"], cfgc.norm_eps)
                if "router" in pp["mlp"]:
                    from repro.models import moe as Moe
                    xx = xx + Moe.moe_apply(pp["mlp"], h,
                                            cfgc.replace(moe_group=1))
                else:
                    xx = xx + Lyr.mlp_apply(pp["mlp"], h, cfgc)
                return xx, kc, vc
            add("layer", cfg.n_layers - 1, dec_fn, (p_abs, x_abs, kc, vc),
                (p_spec, x_spec, kspec, kspec))

    elif fam == "ssm":
        p_abs, p_spec = _layer_template_and_specs(cfg, "layers")
        if kind == "train":
            fwd = lambda pp, xx: Mdl.ssm_layer_fwd(cfgc, pp, xx)[0]
            add("layer", cfg.n_layers - 1, _train_unit(cfgc, fwd),
                (p_abs, x_abs), (p_spec, x_spec))
        elif kind == "prefill":
            fn = lambda pp, xx: Mdl.ssm_layer_fwd(cfgc, pp, xx)[0]
            add("layer", cfg.n_layers - 1, fn, (p_abs, x_abs),
                (p_spec, x_spec))
        else:
            cache, cspecs = abstract_cache(cfg, shape)
            h = SDS(cache["h"].shape[1:], cache["h"].dtype)
            cv = SDS(cache["conv"].shape[1:], cache["conv"].dtype)

            def dec_fn(pp, xx, h0, c0):
                hh = Lyr.rms_norm(xx, pp["ln1"], cfgc.norm_eps)
                y, st = M.mamba_step(pp["ssm"], hh, cfgc, (h0, c0))
                return xx + y, st
            add("layer", cfg.n_layers - 1, dec_fn, (p_abs, x_abs, h, cv),
                (p_spec, x_spec, P(*cspecs["h"][1:]), P(*cspecs["conv"][1:])))

    elif fam == "hybrid":
        g_ids, spans = Mdl.hybrid_split(cfg)
        nW = cfg.n_layers - len(g_ids)
        n_spans = sum(1 for s in spans if s > 0)
        p_abs, p_spec = _layer_template_and_specs(cfg, "swa")
        if kind == "train":
            fwd = lambda pp, xx: Mdl.hybrid_layer_fwd(
                cfgc, pp, xx, pos, window=cfg.swa_window)[0]
            add("swa_layer", nW - n_spans, _train_unit(cfgc, fwd),
                (p_abs, x_abs), (p_spec, x_spec))
        elif kind == "prefill":
            fn = lambda pp, xx: Mdl.hybrid_layer_fwd(
                cfgc, pp, xx, pos, window=cfg.swa_window)[0]
            add("swa_layer", nW - n_spans, fn, (p_abs, x_abs),
                (p_spec, x_spec))
        else:
            cache, cspecs = abstract_cache(cfg, shape)
            args = tuple(SDS(cache[k].shape[1:], cache[k].dtype)
                         for k in ("kw", "vw", "wpos", "hw", "convw"))
            sh = tuple(P(*cspecs[k][1:])
                       for k in ("kw", "vw", "wpos", "hw", "convw"))

            def dec_fn(pp, xx, kc, vc, wp, h0, c0):
                posn = jnp.asarray(S - 1, I32)
                hh = Lyr.rms_norm(xx, pp["ln1"], cfgc.norm_eps)
                o, kc, vc, wp = Mdl._decode_attn_layer(
                    cfgc, pp, hh, kc, vc, posn, posn + 1,
                    window=cfg.swa_window, wpos=wp)
                ao = Lyr.attn_out(pp["attn"], o, cfgc)
                so, st = M.mamba_step(pp["ssm"], hh, cfgc, (h0, c0))
                fused = 0.5 * (Lyr.rms_norm(ao, pp["ln_attn"], cfgc.norm_eps)
                               + Lyr.rms_norm(so, pp["ln_ssm"], cfgc.norm_eps))
                xx = xx + fused
                h2 = Lyr.rms_norm(xx, pp["ln2"], cfgc.norm_eps)
                xx = xx + Lyr.mlp_apply(pp["mlp"], h2, cfgc)
                return xx, kc, vc, wp, st
            add("swa_layer", nW - 1, dec_fn, (p_abs, x_abs) + args,
                (p_spec, x_spec) + sh)

    elif fam == "encdec":
        e_abs, e_spec = _layer_template_and_specs(cfg, "enc")
        d_abs, d_spec = _layer_template_and_specs(cfg, "dec")
        mem_abs = SDS((B, S, cfg.d_model), BF16)
        if kind in ("train", "prefill"):
            Sd = S if kind == "train" else min(S, 4096)
            xd_abs = SDS((B, Sd, cfg.d_model), BF16)
            posd = jnp.arange(Sd)
            enc_fn = lambda pp, xx: Mdl.enc_layer_fwd(cfgc, pp, xx, pos)
            dec_fn = lambda pp, xx, mm: Mdl.dec_layer_fwd(
                cfgc, pp, xx, mm, posd, pos)[0]
            if kind == "train":
                add("enc_layer", cfg.n_enc_layers - 1,
                    _train_unit(cfgc, enc_fn), (e_abs, x_abs),
                    (e_spec, x_spec))

                def dec_unit(pp, xx, mm):
                    f = Lyr.maybe_remat(lambda p2, x2: dec_fn(p2, x2, mm),
                                        cfgc.remat_policy)
                    y, vjp = jax.vjp(f, pp, xx)
                    return vjp(jnp.ones_like(y))
                add("dec_layer", cfg.n_dec_layers - 1, dec_unit,
                    (d_abs, xd_abs, mem_abs), (d_spec, x_spec, x_spec))
            else:
                add("enc_layer", cfg.n_enc_layers - 1, enc_fn,
                    (e_abs, x_abs), (e_spec, x_spec))
                add("dec_layer", cfg.n_dec_layers - 1, dec_fn,
                    (d_abs, xd_abs, mem_abs), (d_spec, x_spec, x_spec))
        else:
            cache, cspecs = abstract_cache(cfg, shape)
            args = tuple(SDS(cache[k].shape[1:], cache[k].dtype)
                         for k in ("k", "v", "ck", "cv"))
            sh = tuple(P(*cspecs[k][1:]) for k in ("k", "v", "ck", "cv"))

            def dec_fn(pp, xx, kc, vc, ck, cv):
                posn = jnp.asarray(min(S, 4096) - 1, I32)
                h = Lyr.rms_norm(xx, pp["ln1"], cfgc.norm_eps)
                o, kc, vc, _ = Mdl._decode_attn_layer(cfgc, pp, h, kc, vc,
                                                      posn, posn + 1)
                xx = xx + Lyr.attn_out(pp["attn"], o, cfgc)
                h = Lyr.rms_norm(xx, pp["lnx"], cfgc.norm_eps)
                qx, _, _ = Lyr.attn_qkv(pp["xattn"], h, cfgc, posn[None, None])
                ox = Lyr.decode_attention(qx, ck, cv, jnp.asarray(S))
                xx = xx + Lyr.attn_out(pp["xattn"], ox, cfgc)
                h = Lyr.rms_norm(xx, pp["ln2"], cfgc.norm_eps)
                xx = xx + Lyr.mlp_apply(pp["mlp"], h, cfgc)
                return xx, kc, vc
            add("dec_layer", cfg.n_dec_layers - 1, dec_fn,
                (d_abs, x_abs) + args, (d_spec, x_spec) + sh)
    return units


def build_bundle(arch: str, shape_name: str) -> StepBundle:
    shape = SHAPES[shape_name]
    cfg = tune_config(load_config(arch), shape)
    notes = (f"residual_shard={cfg.residual_shard} attn_chunk={cfg.attn_chunk}"
             f" remat={cfg.remat_policy}")

    if shape.kind == "train":
        adam = Opt.AdamConfig(
            moment_dtype="bfloat16" if count_params(cfg) > 5e10 else "float32")
        p_abs = Mdl.abstract_params(cfg)
        p_spec = Mdl.param_specs(cfg)
        opt_abs = jax.eval_shape(lambda p: Opt.init_adam(p, adam), p_abs)
        opt_spec = Opt.AdamState(P(), p_spec, p_spec)
        b_abs, b_spec = batch_inputs(cfg, shape, with_labels=True)
        fn = make_train_step(cfg, adam)
        return StepBundle(
            arch, shape_name, "train", fn,
            (p_abs, opt_abs, b_abs), (p_spec, opt_spec, b_spec),
            (p_spec, opt_spec, P()), (0, 1),
            make_cost_units(cfg, shape), model_flops(cfg, shape), notes)

    # serving: params in bf16
    p_abs = Mdl.abstract_params(cfg, dtype="bfloat16")
    p_spec = Mdl.param_specs(cfg)
    if shape.kind == "prefill":
        b_abs, b_spec = batch_inputs(cfg, shape, with_labels=False)
        cache_abs, cache_spec = abstract_cache(cfg, shape)

        def prefill_fn(params, batch):
            return Mdl.prefill(cfg, params, batch, shape.seq_len)
        return StepBundle(
            arch, shape_name, "prefill", prefill_fn,
            (p_abs, b_abs), (p_spec, b_spec), (P(), cache_spec), (),
            make_cost_units(cfg, shape), model_flops(cfg, shape), notes)

    # decode
    cache_abs, cache_spec = abstract_cache(cfg, shape)
    tok_abs = SDS((shape.global_batch, 1), I32)

    def decode_fn(params, cache, token):
        return Mdl.decode_step(cfg, params, cache, token)
    return StepBundle(
        arch, shape_name, "decode", decode_fn,
        (p_abs, cache_abs, tok_abs), (p_spec, cache_spec, ax("batch", None)),
        (P(), cache_spec), (1,),
        make_cost_units(cfg, shape), model_flops(cfg, shape), notes)


# ---------------------------------------------------------------------------
# SVFusion (paper's own architecture) cells
# ---------------------------------------------------------------------------

SVF_SHAPES = {
    "search_10k": dict(n=1_000_000_000, dim=96, degree=32, batch=10240,
                       cache_per_chip=131072),   # Deep1B
    "search_1k": dict(n=200_000_000, dim=100, degree=32, batch=1024,
                      cache_per_chip=131072),    # MSTuring-200M
}


def build_svfusion_bundle(shape_name: str, mesh) -> StepBundle:
    from repro.core.distributed import (analytical_search_flops,
                                        make_distributed_search,
                                        shard_index_arrays)
    from repro.core.types import SearchParams
    p = SVF_SHAPES[shape_name]
    sp = SearchParams(k=10, pool=64, max_iters=64)
    # capacity tier shards over EVERY mesh axis (HBM feasibility at 1B
    # scale); queries replicated, per-shard top-k merged over all axes
    data_axes = tuple(mesh.axis_names)
    n_shards = int(mesh.devices.size)
    idx = shard_index_arrays(p["n"], p["dim"], p["degree"], n_shards,
                             p["cache_per_chip"])
    queries = SDS((p["batch"], p["dim"]), F32)
    key = SDS((2,), jnp.uint32)
    step = make_distributed_search(mesh, sp, data_axes=data_axes,
                                   query_axis=None)
    dspec = data_axes if len(data_axes) > 1 else data_axes[0]
    in_sh = ({k: (P(dspec, None) if v.ndim == 2 else P(dspec))
              for k, v in idx.items()},
             P(None, None), P())
    return StepBundle(
        "svfusion_deep1b" if shape_name == "search_10k" else "svfusion_msturing",
        shape_name, "search", step, (idx, queries, key), in_sh,
        None, (),
        # replicated-query scheme: every shard beam-searches its partition
        # for the whole batch, so useful work scales with n_shards
        [], analytical_search_flops(sp, p["batch"], p["dim"],
                                    p["degree"]) * n_shards,
        f"distributed beam search, {n_shards} shards, queries replicated")
