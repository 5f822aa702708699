"""De-risk spike: 512 host-device mesh, scan-over-layers transformer,
lower+compile timing, memory_analysis/cost_analysis/HLO collective parsing.

Run:  PYTHONPATH=src python scripts/spike_dryrun.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import time
import functools
import re

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

t0 = time.time()
mesh = jax.make_mesh((2, 16, 16), ("pod", "data", "model"))
print(f"mesh build: {time.time()-t0:.2f}s, devices={len(jax.devices())}")

# ---- toy llama-8B-ish scan transformer (abstract weights) ----
L, D, H, KV, DFF, V = 32, 4096, 32, 8, 14336, 128256
HD = D // H
B, S = 256, 512  # keep seq small for the spike


def rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w


def layer(x, w):
    h = rms(x, w["ln1"])
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    a = jnp.einsum("bshk,bthk->bhst", q, k) / jnp.sqrt(HD)
    mask = jnp.tril(jnp.ones((S, S), bool))
    a = jnp.where(mask, a, -1e9)
    a = jax.nn.softmax(a, -1)
    o = jnp.einsum("bhst,bthk->bshk", a, v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, w["wo"])
    h = rms(x, w["ln2"])
    g = jnp.einsum("bsd,df->bsf", h, w["w1"])
    u = jnp.einsum("bsd,df->bsf", h, w["w3"])
    x = x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, w["w2"])
    return x


def model(params, tokens):
    x = params["emb"][tokens]
    def body(x, w):
        return jax.remat(layer)(x, w), None
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms(x, params["lnf"])
    return jnp.einsum("bsd,dv->bsv", x, params["emb_out"])


def loss_fn(params, tokens, labels):
    logits = model(params, tokens)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def train_step(params, tokens, labels):
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
    params = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
    return params, loss


def pspec(tree_spec):
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), tree_spec,
                        is_leaf=lambda x: isinstance(x, P))


param_shapes = {
    "emb": jax.ShapeDtypeStruct((V, D), jnp.bfloat16),
    "emb_out": jax.ShapeDtypeStruct((D, V), jnp.bfloat16),
    "lnf": jax.ShapeDtypeStruct((D,), jnp.bfloat16),
    "layers": {
        "ln1": jax.ShapeDtypeStruct((L, D), jnp.bfloat16),
        "ln2": jax.ShapeDtypeStruct((L, D), jnp.bfloat16),
        "wq": jax.ShapeDtypeStruct((L, D, H, HD), jnp.bfloat16),
        "wk": jax.ShapeDtypeStruct((L, D, KV, HD), jnp.bfloat16),
        "wv": jax.ShapeDtypeStruct((L, D, KV, HD), jnp.bfloat16),
        "wo": jax.ShapeDtypeStruct((L, H, HD, D), jnp.bfloat16),
        "w1": jax.ShapeDtypeStruct((L, D, DFF), jnp.bfloat16),
        "w2": jax.ShapeDtypeStruct((L, DFF, D), jnp.bfloat16),
        "w3": jax.ShapeDtypeStruct((L, D, DFF), jnp.bfloat16),
    },
}
param_spec = {
    "emb": P("model", None),
    "emb_out": P(None, "model"),
    "lnf": P(None),
    "layers": {
        "ln1": P(None, None), "ln2": P(None, None),
        "wq": P(None, None, "model", None),
        "wk": P(None, None, None, "model"),
        "wv": P(None, None, None, "model"),
        "wo": P(None, "model", None, None),
        "w1": P(None, None, "model"),
        "w2": P(None, "model", None),
        "w3": P(None, None, "model"),
    },
}
data_spec = P(("pod", "data"), None)

tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
labels = jax.ShapeDtypeStruct((B, S), jnp.int32)

in_sh = (pspec(param_spec), pspec(data_spec), pspec(data_spec))
out_sh = (pspec(param_spec), pspec(P()))

t0 = time.time()
with mesh:
    lowered = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh).lower(
        param_shapes, tokens, labels)
print(f"lower: {time.time()-t0:.2f}s")

t0 = time.time()
compiled = lowered.compile()
print(f"compile: {time.time()-t0:.2f}s")

ma = compiled.memory_analysis()
print("memory_analysis:", ma)
ca = compiled.cost_analysis()
print("cost keys:", sorted(k for k in ca.keys())[:20] if hasattr(ca, 'keys') else type(ca))
print("flops:", ca.get("flops") if hasattr(ca, "get") else None)
print("bytes accessed:", ca.get("bytes accessed") if hasattr(ca, "get") else None)

t0 = time.time()
hlo = compiled.as_text()
print(f"as_text: {time.time()-t0:.2f}s, len={len(hlo)}")
colls = re.findall(r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)", hlo)
from collections import Counter
print("collectives:", Counter(colls))
