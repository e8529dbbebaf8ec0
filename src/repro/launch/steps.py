"""Step-artifact builders shared by the dry-run, the drivers and benchmarks.

For every (architecture x assigned shape) cell this module produces the
jit-able step function plus abstract inputs (ShapeDtypeStructs — never
allocated) and explicit in/out shardings for the production mesh:

  train_4k     -> train_step(params, opt_state, batch)
  prefill_32k  -> prefill_step(params, tokens[, frames], caches)
  decode_32k   -> serve_step(params, last_tokens, caches)   (one new token)
  long_500k    -> serve_step with a 524288-token state (SSM/hybrid only)

`input_specs(arch, shape)` is the deliverable-(e) entry point.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, ShapeSpec, get_config
from repro.distributed import sharding as sh
from repro.models import transformer as TF
from repro.models.config import ModelConfig
from repro.training.optimizer import AdamWConfig, adamw_abstract
from repro.training.train_step import batch_axes, build_train_step, make_batch_abstract

BIG_PARAMS = 100e9  # >=100B: bf16 Adam moments (memory budget, DESIGN §5)


def make_rules(cfg: ModelConfig, mesh) -> sh.ShardingRules:
    return sh.ShardingRules(mesh).with_overrides(cfg.sharding_overrides)


def opt_config_for(cfg: ModelConfig) -> AdamWConfig:
    big = cfg.approx_params() >= BIG_PARAMS
    return AdamWConfig(moment_dtype=jnp.bfloat16 if big else jnp.float32)


@dataclasses.dataclass
class StepArtifacts:
    """Everything needed to lower one cell."""

    fn: Callable
    args: tuple  # abstract ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any
    donate: tuple[int, ...] = ()


def _named(rules: sh.ShardingRules, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------


def params_abstract(cfg: ModelConfig):
    return sh.abstract_from_template(TF.param_template(cfg))


def input_specs(arch: str, shape: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell
    (deliverable e.2) — weak-type-correct, shardable, no device allocation."""
    cfg = get_config(arch)
    sp = SHAPES[shape]
    if sp.kind == "train":
        return make_batch_abstract(cfg, sp.global_batch, sp.seq_len)
    if sp.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((sp.global_batch, sp.seq_len), jnp.int32)}
        if cfg.family in ("vlm", "encdec"):
            nf = cfg.n_frontend_tokens or 64
            out["frames"] = jax.ShapeDtypeStruct((sp.global_batch, nf, cfg.d_model), cfg.dtype)
        return out
    # decode kinds: one new token against a seq_len cache
    return {
        "last_tokens": jax.ShapeDtypeStruct((sp.global_batch,), jnp.int32),
        "caches": TF.init_caches(cfg, sp.global_batch, sp.seq_len, abstract=True),
    }


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------


def build_train_artifacts(cfg: ModelConfig, sp: ShapeSpec, rules: sh.ShardingRules) -> StepArtifacts:
    opt_cfg = opt_config_for(cfg)
    tmpl = TF.param_template(cfg)
    p_abs = sh.abstract_from_template(tmpl)
    p_spec = sh.specs_from_template(tmpl, rules)
    o_abs = adamw_abstract(p_abs, opt_cfg)
    o_spec = {"m": p_spec, "v": p_spec, "step": P()}
    b_abs = make_batch_abstract(cfg, sp.global_batch, sp.seq_len)
    b_spec = sh.specs_for_axes(b_abs, batch_axes(cfg), rules)

    raw_step = build_train_step(cfg, opt_cfg)

    def train_step(params, opt_state, batch):
        with sh.use_sharding_rules(rules):
            return raw_step(params, opt_state, batch)

    metrics_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
    return StepArtifacts(
        fn=train_step,
        args=(p_abs, o_abs, b_abs),
        in_shardings=(_named(rules, p_spec), _named(rules, o_spec), _named(rules, b_spec)),
        out_shardings=(
            _named(rules, p_spec),
            _named(rules, o_spec),
            _named(rules, metrics_spec),
        ),
        donate=(0, 1),
    )


def build_prefill_artifacts(cfg: ModelConfig, sp: ShapeSpec, rules: sh.ShardingRules) -> StepArtifacts:
    tmpl = TF.param_template(cfg)
    p_abs = sh.abstract_from_template(tmpl)
    p_spec = sh.specs_from_template(tmpl, rules)
    c_abs = TF.init_caches(cfg, sp.global_batch, sp.seq_len, abstract=True)
    c_spec = sh.specs_for_axes(c_abs, TF.cache_axes(cfg), rules)
    ins = input_specs(cfg.name, sp.name)
    tok_spec = rules.spec_for_shape(ins["tokens"].shape, ("batch", "seq"))
    frames = ins.get("frames")

    if frames is not None:
        f_spec = rules.spec_for_shape(frames.shape, ("batch", "seq", "act_d_model"))

        def prefill_step(params, tokens, frames, caches):
            with sh.use_sharding_rules(rules):
                return TF.prefill(cfg, params, tokens, caches, frames)

        args = (p_abs, ins["tokens"], frames, c_abs)
        in_sh = (
            _named(rules, p_spec),
            NamedSharding(rules.mesh, tok_spec),
            NamedSharding(rules.mesh, f_spec),
            _named(rules, c_spec),
        )
    else:

        def prefill_step(params, tokens, caches):
            with sh.use_sharding_rules(rules):
                return TF.prefill(cfg, params, tokens, caches)

        args = (p_abs, ins["tokens"], c_abs)
        in_sh = (
            _named(rules, p_spec),
            NamedSharding(rules.mesh, tok_spec),
            _named(rules, c_spec),
        )

    next_spec = rules.spec_for_shape((sp.global_batch,), ("batch",))
    return StepArtifacts(
        fn=prefill_step,
        args=args,
        in_shardings=in_sh,
        out_shardings=(NamedSharding(rules.mesh, next_spec), _named(rules, c_spec)),
        donate=(len(args) - 1,),
    )


def build_decode_artifacts(cfg: ModelConfig, sp: ShapeSpec, rules: sh.ShardingRules) -> StepArtifacts:
    tmpl = TF.param_template(cfg)
    p_abs = sh.abstract_from_template(tmpl)
    p_spec = sh.specs_from_template(tmpl, rules)
    c_abs = TF.init_caches(cfg, sp.global_batch, sp.seq_len, abstract=True)
    c_spec = sh.specs_for_axes(c_abs, TF.cache_axes(cfg), rules)
    last_abs = jax.ShapeDtypeStruct((sp.global_batch,), jnp.int32)
    last_spec = rules.spec_for_shape((sp.global_batch,), ("batch",))

    def serve_step(params, last_tokens, caches):
        with sh.use_sharding_rules(rules):
            return TF.decode_step(cfg, params, last_tokens, caches)

    return StepArtifacts(
        fn=serve_step,
        args=(p_abs, last_abs, c_abs),
        in_shardings=(
            _named(rules, p_spec),
            NamedSharding(rules.mesh, last_spec),
            _named(rules, c_spec),
        ),
        out_shardings=(NamedSharding(rules.mesh, last_spec), _named(rules, c_spec)),
        donate=(2,),
    )


def build_cell(arch: str, shape: str, mesh) -> StepArtifacts:
    cfg = get_config(arch)
    sp = SHAPES[shape]
    rules = make_rules(cfg, mesh)
    if sp.kind == "train":
        return build_train_artifacts(cfg, sp, rules)
    if sp.kind == "prefill":
        return build_prefill_artifacts(cfg, sp, rules)
    if sp.kind in ("decode", "long_decode"):
        return build_decode_artifacts(cfg, sp, rules)
    raise ValueError(sp.kind)
