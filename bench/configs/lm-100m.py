"""lm-100m: the repository's own ~100M-parameter decoder LM at full width.

Sizes are in ``lm-100m.json``. This file builds the system under test from
them (the program's ``FederatedRunner`` around ``repro.models.transformer``'s
loss, Adam, the program's federated batcher), makes the weights from the
seed in the program's parameter layout, counts the model FLOPs, and holds
the plain reference model.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _arch(sizes):
    from repro.configs.paper import LM_100M

    return dataclasses.replace(
        LM_100M,
        num_layers=sizes["num_layers"], d_model=sizes["d_model"], num_heads=sizes["num_heads"],
        num_kv_heads=sizes["num_kv_heads"], head_dim=sizes["head_dim"], d_ff=sizes["d_ff"],
        vocab_size=sizes["vocab_size"], rope_theta=sizes["rope_theta"], norm_eps=sizes["norm_eps"],
        attn_chunk=sizes["attn_chunk"], param_dtype=sizes["param_dtype"],
        compute_dtype=sizes["compute_dtype"], remat="none", scan_layers=True,
    )


def make_batch(arrays, rows):
    toks = arrays["tokens"][rows]
    return {"inputs": toks[..., :-1], "targets": toks[..., 1:]}


def make_runner(sizes, traffic, data, seed, mesh):
    from repro.core import FedTopology, HierFAVGConfig
    from repro.data import FederatedBatcher
    from repro.fed import FederatedRunner, RunnerConfig
    from repro.models import transformer
    from repro.optim import adam

    batcher = FederatedBatcher(
        data["arrays"], data["parts"], batch_size=traffic["batch_size"], seed=seed,
        batch_fn=lambda b: {"inputs": b["tokens"][..., :-1], "targets": b["tokens"][..., 1:]},
    )
    k1, k2 = traffic["kappas"]
    return FederatedRunner(
        loss_fn=transformer.make_loss_fn(_arch(sizes)),
        optimizer=adam(sizes["lr"], b1=sizes["adam_b1"], b2=sizes["adam_b2"], eps=sizes["adam_eps"]),
        topology=FedTopology(num_edges=traffic["num_edges"], clients_per_edge=traffic["clients_per_edge"]),
        hier_config=HierFAVGConfig(kappa1=k1, kappa2=k2),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(num_rounds=0, engine="superround"),
        mesh=mesh,
    )


def init_params(sizes, seed):
    """Weights from the seed, in one jitted call, in the program's layout:
    layers stacked on a leading axis under ``blocks/b0``."""
    L, d, V, ff = sizes["num_layers"], sizes["d_model"], sizes["vocab_size"], sizes["d_ff"]
    hq, hkv = sizes["num_heads"] * sizes["head_dim"], sizes["num_kv_heads"] * sizes["head_dim"]
    shapes = {
        "embed": ((V, d), 1.0),
        "blocks": {"b0": {
            "norm1": ((L, d), None),
            "attn": {"wq": ((L, d, hq), d ** -0.5), "wk": ((L, d, hkv), d ** -0.5),
                     "wv": ((L, d, hkv), d ** -0.5), "wo": ((L, hq, d), hq ** -0.5)},
            "norm2": ((L, d), None),
            "mlp": {"w1": ((L, d, ff), d ** -0.5), "w3": ((L, d, ff), d ** -0.5),
                    "w2": ((L, ff, d), ff ** -0.5)},
        }},
        "final_norm": ((d,), None),
        "lm_head": ((d, V), d ** -0.5),
    }
    leaves, tree = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = [jnp.ones(s, jnp.float32) if std is None else jax.random.normal(k, s, jnp.float32) * std
               for k, (s, std) in zip(keys, leaves)]
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.key(seed))


def first_reading(state, params0):
    """What Adam made of the first interval's gradients: its first moment
    (a tree, and nothing to subtract)."""
    return state.opt_state[0].mu, None


def model_flops_per_interval(sizes, traffic):
    """6 FLOPs per parameter per token (forward 2, backward 4) over every
    matmul weight (the layers and the LM head; the embedding is a gather),
    plus attention's two score matmuls in full, as the program computes them
    (no causal skipping): 12 * layers * seq * heads * head_dim per token."""
    L, d, ff, V = sizes["num_layers"], sizes["d_model"], sizes["d_ff"], sizes["vocab_size"]
    hq, hkv = sizes["num_heads"] * sizes["head_dim"], sizes["num_kv_heads"] * sizes["head_dim"]
    matmul_params = L * (d * hq + 2 * d * hkv + hq * d + 3 * d * ff) + d * V
    T = traffic["seq_len"]
    per_token = 6 * matmul_params + 12 * L * T * hq
    k1, k2 = traffic["kappas"]
    clients = traffic["num_edges"] * traffic["clients_per_edge"]
    tokens = clients * traffic["batch_size"] * T * k1 * k2
    return float(per_token * tokens)


# ---------------------------------------------------------------------------
# plain reference model
# ---------------------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(x.dtype)


def _rope(x, theta):
    """x: (b, S, H, hd); rotates the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def reference_loss(sizes):
    H, Hkv, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]

    def loss(p, batch, dt):
        tokens, targets = batch["inputs"], batch["targets"]
        b, S = tokens.shape
        x = p["embed"][tokens].astype(dt)
        causal = jnp.tril(jnp.ones((S, S), bool))

        def layer(x, lp):
            h = _rmsnorm(x, lp["norm1"], eps)
            q = _rope((h @ lp["attn"]["wq"]).reshape(b, S, H, hd), theta)
            k = _rope((h @ lp["attn"]["wk"]).reshape(b, S, Hkv, hd), theta)
            v = (h @ lp["attn"]["wv"]).reshape(b, S, Hkv, hd)
            k = jnp.repeat(k, H // Hkv, axis=2)  # query head i reads kv head i // (H / Hkv)
            v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(hd ** -0.5, dt)
            s = jnp.where(causal, s, jnp.asarray(-1e30 if dt == jnp.float32 else -1e9, dt))
            a = jax.nn.softmax(s, axis=-1)
            x = x + jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, S, H * hd) @ lp["attn"]["wo"]
            h = _rmsnorm(x, lp["norm2"], eps)
            m = lp["mlp"]
            return x + (jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])) @ m["w2"], None

        x, _ = jax.lax.scan(layer, x, p["blocks"]["b0"])
        logits = (_rmsnorm(x, p["final_norm"], eps) @ p["lm_head"]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    return loss


def reference(sizes, traffic, data, seed, devices, variant=None):
    from bench import reference as ref

    return ref.run(
        reference_loss(sizes), init_params(sizes, seed),
        optimizer={"kind": "adam", "lr": sizes["lr"], "b1": sizes["adam_b1"],
                   "b2": sizes["adam_b2"], "eps": sizes["adam_eps"]},
        traffic=traffic, data=data, seed=seed, make_batch=make_batch, devices=devices,
        variant=variant,
    )
