"""Step functions + abstract specs for every (arch × shape) cell.

Everything here is shape-driven: the dry-run lowers these with
ShapeDtypeStruct stand-ins (no allocation); examples/tests call them with
real arrays on tiny configs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig
from repro.dist import sharding as sh
from repro.models import lm, template as T
from repro.optim import AdamW

F32 = jnp.float32

# per-device bytes we budget for scan-saved activation carries (v5e ~16GB)
CARRY_BUDGET = 6e9


# ------------------------------------------------------------------ batch specs
def batch_template(cfg: ModelConfig, shape_name: str, rows: int | None = None):
    """ParamSpec tree describing the *global* input batch of a cell."""
    seq, gb, kind = SHAPES[shape_name]
    if rows is not None:
        gb = rows
    t = {}
    if kind == "decode":
        if cfg.frame_input_dim:
            raise ValueError("encoder archs have no decode step")
        t["tokens"] = T.ParamSpec((gb, 1), ("batch", None), jnp.int32, "zeros")
        return t
    if cfg.frame_input_dim:
        t["frames"] = T.ParamSpec((gb, seq, cfg.frame_input_dim),
                                  ("batch", "seq", None), jnp.bfloat16, "normal")
    else:
        t["tokens"] = T.ParamSpec((gb, seq), ("batch", "seq"), jnp.int32, "zeros")
    if kind == "train":
        t["labels"] = T.ParamSpec((gb, seq), ("batch", "seq"), jnp.int32, "zeros")
    if cfg.vision_dim:
        t["vision"] = T.ParamSpec((gb, cfg.vision_tokens, cfg.vision_dim),
                                  ("batch", None, None), jnp.bfloat16, "normal")
    return t


def serve_param_template(cfg: ModelConfig, weight_dtype: str = "bf16"):
    """Inference weights: bf16, or W8A16 (int8 matrix weights dequantised at
    use; per-channel scales add <1% bytes and are omitted from the dry-run
    shape model)."""
    int8 = weight_dtype == "int8"

    def conv(s):
        if int8 and len(s.shape) >= 2:
            return dataclasses.replace(s, dtype=jnp.int8)
        return dataclasses.replace(s, dtype=jnp.bfloat16)

    return jax.tree.map(conv, lm.model_template(cfg), is_leaf=T.is_spec)


def serve_overrides(cfg: ModelConfig, model_shards: int = 16) -> dict:
    """Serving sharding policy: replicate weights across 'data' (pure TP,
    no per-token FSDP all-gathers) whenever the bf16 weights fit one TP
    group's HBM; the MoE/90B giants keep 2D weight sharding (weight-gather
    serving) on non-EP meshes. On an EP mesh (``make_production_mesh(
    ep=True)``) the ``experts`` rule resolves and MoE expert weights shard
    E-ways over 'expert' with no override needed."""
    out: dict = {}
    bf16_bytes = cfg.param_count() * 2
    if bf16_bytes / model_shards < 10e9:
        out["fsdp"] = None
    if cfg.n_heads and cfg.n_heads % model_shards:
        # kv heads can't shard over 'model': shard the cache SEQ dim there
        # instead of replicating the whole KV cache 16x per device
        out["cache_seq"] = "model"
    return out


def opt_state_template(cfg: ModelConfig):
    pt = lm.model_template(cfg)
    return {
        "m": pt,
        "v": pt,
        "step": T.ParamSpec((), (), jnp.int32, "zeros"),
    }


def train_state_template(cfg: ModelConfig):
    return {"params": lm.model_template(cfg), "opt": opt_state_template(cfg)}


def input_specs(cfg: ModelConfig, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    return T.abstract_from_template(batch_template(cfg, shape_name))


# ------------------------------------------------------------------ grad accum
def pick_grad_accum(cfg: ModelConfig, shape_name: str,
                    ruleset: Optional[sh.Ruleset] = None) -> int:
    seq, gb, kind = SHAPES[shape_name]
    if kind != "train":
        return 1
    rs = ruleset or sh.active()
    dp = 1
    if rs is not None:
        dp = rs.axis_size("data") * rs.axis_size("pod")
    carry = cfg.n_layers * cfg.d_model * 2 * gb * seq / max(dp, 1)
    if any(b.kind == "ssd" for b in cfg.pattern):
        # SSD within-chunk tiles: ~two dozen fp32 (tokens*nh*L_chunk) buffers
        # live during one layer's backward (the Pallas ssd kernel keeps these
        # in VMEM on real TPU; the XLA fallback materialises them)
        nh = max(cfg.d_inner // max(cfg.ssm_headdim, 1), 1)
        ssd = 24 * 4 * (gb * seq / max(dp, 1)) * nh * cfg.ssm_chunk
        carry = max(carry, ssd)
    if any(b.kind == "rec" for b in cfg.pattern):
        # RG-LRU associative scans hold ~2 fp32 tensors per log2(seq) level
        # transiently during the backward pass of each microbatch
        levels = max(1, math.ceil(math.log2(max(seq, 2))))
        assoc = 2 * 4 * levels * gb * seq * (cfg.lru_width or cfg.d_model)
        carry = max(carry, assoc / max(dp, 1))
    need = max(1, math.ceil(carry / CARRY_BUDGET))
    n = 1
    while n < need:
        n *= 2
    # keep at least one example per data shard in each microbatch
    n = min(n, max(1, gb // max(dp, 1)))
    while gb % n:
        n //= 2
    return max(n, 1)


# ------------------------------------------------------------------ steps
def make_train_step(cfg: ModelConfig, opt: AdamW, *, remat="full",
                    grad_accum: int = 1, unroll: bool = False,
                    bf16_gather: bool = False):
    def loss_fn(params, mb):
        loss, metrics = lm.lm_loss(cfg, params, mb, remat=remat, unroll=unroll)
        return loss, metrics

    def maybe_cast(params):
        # §Perf optimization: casting the stacked fp32 master weights to bf16
        # BEFORE the layer scan halves every FSDP all-gather inside it (the
        # gather then moves bf16 slices); grads still flow to fp32 masters.
        if not bf16_gather:
            return params
        return jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 else p, params)

    # §Perf: pin every per-microbatch gradient to the parameter sharding so
    # the cross-'data' reduction lowers as reduce-scatter onto the FSDP
    # shards (≈1x bytes) instead of all-reduce of the full tensor (≈2x).
    ptmpl = lm.model_template(cfg)

    def shard_grads(g):
        if sh.active() is None:
            return g
        return jax.tree.map(lambda gg, s: sh.constrain(gg, s.axes), g, ptmpl)

    def train_step(state, batch):
        params = state["params"]

        def cast_loss_fn(p, mb):
            return loss_fn(maybe_cast(p), mb)

        if grad_accum == 1:
            (loss, _), grads = jax.value_and_grad(cast_loss_fn, has_aux=True)(
                params, batch)
            grads = shard_grads(grads)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def micro(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(cast_loss_fn, has_aux=True)(
                    params, mb)
                g = shard_grads(g)
                gsum = jax.tree.map(lambda a, b: a + b.astype(F32), gsum, g)
                return (gsum, lsum + l), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            (gsum, lsum), _ = lax.scan(micro, (zeros, jnp.zeros((), F32)), mbs)
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
        new_params, new_opt, om = opt.update(grads, state["opt"], params)
        metrics = {"loss": loss.astype(F32), **om}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, max_seq: int, unroll: bool = False):
    def prefill_step(params, batch):
        logits, _, cache = lm.forward(cfg, params, batch, mode="prefill",
                                      remat="none", logits_mode="last",
                                      max_seq=max_seq, unroll=unroll)
        return logits, cache

    return prefill_step


def make_decode_step(cfg: ModelConfig, unroll: bool = False):
    def decode_step(params, cache, tokens, pos):
        return lm.decode_step(cfg, params, cache, tokens, pos, unroll=unroll)

    return decode_step


def make_grad_step(cfg: ModelConfig, *, remat="full", unroll=False):
    """value_and_grad only (no optimizer) — used to isolate the optimizer
    term in roofline calibration."""

    def grad_step(params, batch):
        def loss_fn(p):
            return lm.lm_loss(cfg, p, batch, remat=remat, unroll=unroll)[0]

        return jax.value_and_grad(loss_fn)(params)

    return grad_step


# ------------------------------------------------------------------ cell assembly
@dataclasses.dataclass
class Cell:
    """Everything needed to lower one (arch × shape) combination."""

    cfg: ModelConfig
    shape_name: str
    step_fn: object
    in_abstract: tuple
    in_shardings: tuple
    out_shardings: object
    donate_argnums: tuple
    grad_accum: int
    static_meta: dict


def _shardings(tmpl, rs):
    return T.shardings_from_template(tmpl, rs)


def build_cell(cfg: ModelConfig, shape_name: str, rs: sh.Ruleset, *,
               remat="full", grad_accum: Optional[int] = None,
               bf16_gather: bool = False,
               weight_dtype: str = "bf16") -> Cell:
    seq, gb, kind = SHAPES[shape_name]
    bt = batch_template(cfg, shape_name)
    if kind == "train":
        ga = grad_accum or pick_grad_accum(cfg, shape_name, rs)
        st = train_state_template(cfg)
        opt = AdamW()
        step = make_train_step(cfg, opt, remat=remat, grad_accum=ga,
                               bf16_gather=bf16_gather)
        state_sh = _shardings(st, rs)
        repl = NamedSharding(rs.mesh, P())
        metrics_sh = {"loss": repl, "grad_norm": repl, "lr": repl}
        return Cell(cfg, shape_name, step,
                    (T.abstract_from_template(st), T.abstract_from_template(bt)),
                    (state_sh, _shardings(bt, rs)),
                    (state_sh, metrics_sh), (0,), ga,
                    {"kind": kind, "seq": seq, "global_batch": gb})
    pt = serve_param_template(cfg, weight_dtype)
    if kind == "prefill":
        step = make_prefill_step(cfg, max_seq=seq)
        ct = lm.cache_template(cfg, gb, seq)
        return Cell(cfg, shape_name, step,
                    (T.abstract_from_template(pt), T.abstract_from_template(bt)),
                    (_shardings(pt, rs), _shardings(bt, rs)),
                    (None, _shardings(ct, rs)), (), 1,
                    {"kind": kind, "seq": seq, "global_batch": gb})
    # decode: one new token against a cache of length seq
    step = make_decode_step(cfg)
    ct = lm.cache_template(cfg, gb, seq)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    repl = NamedSharding(rs.mesh, P())
    return Cell(cfg, shape_name, step,
                (T.abstract_from_template(pt), T.abstract_from_template(ct),
                 T.abstract_from_template(bt)["tokens"], pos),
                (_shardings(pt, rs), _shardings(ct, rs),
                 _shardings(bt, rs)["tokens"], repl),
                (None, _shardings(ct, rs)), (1,), 1,
                {"kind": kind, "seq": seq, "global_batch": gb})


def build_calibration_cell(cfg: ModelConfig, shape_name: str, rs: sh.Ruleset,
                           *, n_layers: int, variant: str, remat="full",
                           micro_rows: Optional[int] = None,
                           bf16_gather: bool = False) -> Cell:
    """Unrolled reduced-layer cell for cost calibration.

    variant: 'train' (one full step, ga=1) | 'grad' (no optimizer) |
             'prefill' | 'decode'. micro_rows replaces the global batch for
    train variants (the per-microbatch row count)."""
    cfg_k = dataclasses.replace(cfg, n_layers=n_layers)
    seq, gb, kind = SHAPES[shape_name]
    rows = micro_rows if kind == "train" else None
    bt = batch_template(cfg_k, shape_name, rows)
    meta = {"kind": kind, "seq": seq, "global_batch": rows or gb,
            "calibration": variant, "n_layers": n_layers}
    if variant == "train":
        st = train_state_template(cfg_k)
        step = make_train_step(cfg_k, AdamW(), remat=remat, grad_accum=1,
                               unroll=True, bf16_gather=bf16_gather)
        return Cell(cfg_k, shape_name, step,
                    (T.abstract_from_template(st), T.abstract_from_template(bt)),
                    (_shardings(st, rs), _shardings(bt, rs)),
                    None, (0,), 1, meta)
    if variant == "grad":
        pt = lm.model_template(cfg_k)
        step = make_grad_step(cfg_k, remat=remat, unroll=True)
        return Cell(cfg_k, shape_name, step,
                    (T.abstract_from_template(pt), T.abstract_from_template(bt)),
                    (_shardings(pt, rs), _shardings(bt, rs)),
                    None, (), 1, meta)
    pt = serve_param_template(cfg_k)
    if variant == "prefill":
        step = make_prefill_step(cfg_k, max_seq=seq, unroll=True)
        return Cell(cfg_k, shape_name, step,
                    (T.abstract_from_template(pt), T.abstract_from_template(bt)),
                    (_shardings(pt, rs), _shardings(bt, rs)),
                    None, (), 1, meta)
    if variant == "decode":
        step = make_decode_step(cfg_k, unroll=True)
        ct = lm.cache_template(cfg_k, gb, seq)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        repl = NamedSharding(rs.mesh, P())
        return Cell(cfg_k, shape_name, step,
                    (T.abstract_from_template(pt), T.abstract_from_template(ct),
                     T.abstract_from_template(bt)["tokens"], pos),
                    (_shardings(pt, rs), _shardings(ct, rs),
                     _shardings(bt, rs)["tokens"], repl),
                    None, (1,), 1, meta)
    raise ValueError(variant)


def lower_cell(cell: Cell, mesh, overrides: Optional[dict] = None):
    """Trace + lower the cell's step under the mesh's rules. Returns Lowered."""
    with sh.use_rules(mesh, overrides):
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        )
        return jitted.lower(*cell.in_abstract)


# Keep per-layer FSDP all-gathers inside the layer scan: XLA's while-loop LICM
# otherwise hoists them, materialising every layer's gathered weights at once
# (observed: qwen2-72b train temp 19.5GB -> 10.0GB with the pass disabled).
# On real TPU deployments the same is controlled via collective-pipeliner
# tuning; for the AOT dry-run this keeps the memory model deployment-faithful.
COMPILER_OPTS = {"xla_disable_hlo_passes": "while-loop-invariant-code-motion"}
