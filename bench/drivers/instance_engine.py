"""Drives ``repro.serving.engine.InstanceEngine`` through its public API,
the way ``launch/serve.py:run_colocated`` does: ``submit(ServeRequest)``
when a request is due, then ``step()``.

The program's ``ModelConfig`` is built from the configuration file alone,
and its parameter tree is filled by one jitted call from the seed
(``bench.lib.weights``), leaf by leaf under the names the reference uses.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import spec as _spec
from bench.lib.weights import Leaf, draw, seed_words
from bench.reference.common import common_leaves

sys.path.insert(0, os.path.join(_spec.ROOT, "src"))

from repro.distributed.sharding import TensorSpec  # noqa: E402
from repro.models import transformer as TF  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving.engine import InstanceEngine, ServeRequest  # noqa: E402


def model_config(c: dict) -> ModelConfig:
    kw = dict(
        name=c["name"],
        family="dense",
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        mlp="swiglu",
        attn=c["attention"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c.get("attention_bias", False)),
        dtype=jnp.bfloat16,
        microbatches=1,
    )
    if c["attention"] == "gqa":
        kw["head_dim"] = int(c.get("head_dim") or kw["d_model"] // kw["n_heads"])
    elif c["attention"] == "mla":
        kw.update(
            q_lora_rank=int(c["q_lora_rank"]),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_dim=int(c["qk_nope_head_dim"]),
            qk_rope_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
        )
    else:
        raise ValueError(f"unknown attention {c['attention']!r}")
    return ModelConfig(**kw)


def param_builder(c: dict, attn_mod, mcfg: ModelConfig):
    """A jitted ``seed words -> parameter tree`` for the program's layout."""
    model_leaves, layer_leaves = common_leaves(c)
    layer_leaves = {**layer_leaves, **attn_mod.leaves(c)}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        TF.param_template(mcfg), is_leaf=lambda x: isinstance(x, TensorSpec))
    plan = []
    for path, ts in flat:
        keys = [p.key for p in path]
        stacked = keys[0] == "layers"
        name = ".".join(keys[1:] if stacked else keys)
        table = layer_leaves if stacked else model_leaves
        if name not in table:
            raise KeyError(f"program leaf {name!r} has no weight in the reference")
        leaf = table[name]
        shape = ts.shape[1:] if stacked else ts.shape
        # the program pads the vocabulary axis; every other axis must agree
        same = [a == b or b == c["vocab_size"] for a, b in zip(shape, leaf.shape)]
        if len(shape) != len(leaf.shape) or not all(same):
            raise ValueError(f"leaf {name}: program {shape} vs reference {leaf.shape}")
        plan.append((name, Leaf(tuple(shape), leaf.kind, leaf.fan_in_axes), ts.dtype,
                     ts.shape[0] if stacked else 0))

    @jax.jit
    def build(words):
        return jax.tree_util.tree_unflatten(
            treedef, [draw(words, n, lf, dt, n_layers=nl) for n, lf, dt, nl in plan])

    return build


class Server:
    """One engine with its weights; the client loop sees only this."""

    def __init__(self, cell, seed: int):
        self.mcfg = model_config(cell.config)
        build = param_builder(cell.config, cell.reference(), self.mcfg)
        self.params = build(jnp.asarray(seed_words(seed)))
        jax.block_until_ready(self.params)
        eng = cell.cell["engine"]
        self.engine = InstanceEngine(self.mcfg, self.params, n_slots=int(eng["n_slots"]),
                                     max_seq=int(eng["max_seq"]))
        self.n_slots = self.engine.n_slots

    def submit(self, rid: int, prompt: np.ndarray, n_out: int) -> ServeRequest:
        req = ServeRequest(rid=rid, prompt=prompt, max_new_tokens=n_out)
        self.engine.submit(req)
        return req

    def step(self) -> list[ServeRequest]:
        return self.engine.step()

    @staticmethod
    def tokens(req: ServeRequest) -> list[int]:
        return req.out_tokens

    @staticmethod
    def admitted(req: ServeRequest) -> bool:
        return req.slot is not None

    def close(self) -> None:
        """Free the program's state before the reference runs."""
        del self.engine, self.params
