"""Spike 2: validate cost-probe (unrolled L=1/L=2 linear extrapolation)
against fully-unrolled ground truth; confirm scan body counted once."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import time, re
from collections import Counter
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

D, H, KV, DFF, V = 256, 8, 4, 512, 1024
HD = D // H
B, S = 8, 128


def rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w


def layer(x, w):
    h = rms(x, w["ln1"])
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"]).reshape(B, S, KV, H // KV, HD)
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"])
    a = jnp.einsum("bskgh,btkh->bkgst", q, k) / jnp.sqrt(HD)
    mask = jnp.tril(jnp.ones((S, S), bool))
    a = jnp.where(mask[None, None, None], a, -1e9)
    a = jax.nn.softmax(a, -1)
    o = jnp.einsum("bkgst,btkh->bskgh", a, v).reshape(B, S, H, HD)
    x = x + jnp.einsum("bshk,hkd->bsd", o, w["wo"])
    h = rms(x, w["ln2"])
    x = x + jnp.einsum("bsf,fd->bsd",
                       jax.nn.silu(jnp.einsum("bsd,df->bsf", h, w["w1"]))
                       * jnp.einsum("bsd,df->bsf", h, w["w3"]), w["w2"])
    return x


def model(params, tokens, L, scan):
    x = params["emb"][tokens]
    if scan:
        def body(x, w):
            return jax.remat(layer)(x, w), None
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for i in range(L):
            w = jax.tree.map(lambda a: a[i], params["layers"])
            x = jax.remat(layer)(x, w)
    x = rms(x, params["lnf"])
    return jnp.einsum("bsd,dv->bsv", x, params["emb_out"])


def make_loss(L, scan):
    def loss_fn(params, tokens, labels):
        logits = model(params, tokens, L, scan)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))
    def train_step(params, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        return jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads), loss
    return train_step


def shapes(L):
    f = jnp.bfloat16
    return {
        "emb": jax.ShapeDtypeStruct((V, D), f),
        "emb_out": jax.ShapeDtypeStruct((D, V), f),
        "lnf": jax.ShapeDtypeStruct((D,), f),
        "layers": {
            "ln1": jax.ShapeDtypeStruct((L, D), f),
            "ln2": jax.ShapeDtypeStruct((L, D), f),
            "wq": jax.ShapeDtypeStruct((L, D, H, HD), f),
            "wk": jax.ShapeDtypeStruct((L, D, KV, HD), f),
            "wv": jax.ShapeDtypeStruct((L, D, KV, HD), f),
            "wo": jax.ShapeDtypeStruct((L, H, HD, D), f),
            "w1": jax.ShapeDtypeStruct((L, D, DFF), f),
            "w2": jax.ShapeDtypeStruct((L, DFF, D), f),
            "w3": jax.ShapeDtypeStruct((L, D, DFF), f),
        },
    }


SPEC = {
    "emb": P("model", None), "emb_out": P(None, "model"), "lnf": P(None),
    "layers": {
        "ln1": P(None, None), "ln2": P(None, None),
        "wq": P(None, None, "model", None),
        "wk": P(None, None, "model", None),
        "wv": P(None, None, "model", None),
        "wo": P(None, "model", None, None),
        "w1": P(None, None, "model"),
        "w2": P(None, "model", None),
        "w3": P(None, None, "model"),
    },
}


def lower_cell(L, scan):
    ts = make_loss(L, scan)
    ns = lambda s: jax.sharding.NamedSharding(mesh, s)
    in_sh = (jax.tree.map(ns, SPEC, is_leaf=lambda x: isinstance(x, P)),
             ns(P("data", None)), ns(P("data", None)))
    out_sh = (jax.tree.map(ns, SPEC, is_leaf=lambda x: isinstance(x, P)), ns(P()))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    t0 = time.time()
    with mesh:
        lo = jax.jit(ts, in_shardings=in_sh, out_shardings=out_sh).lower(
            shapes(L), tok, tok)
        co = lo.compile()
    dt = time.time() - t0
    ca = co.cost_analysis()
    hlo = co.as_text()
    colls = Counter(re.findall(
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(", hlo))
    return dict(t=dt, flops=ca["flops"], bytes=ca["bytes accessed"],
                colls=colls, hlo_len=len(hlo))


r1 = lower_cell(1, scan=False)
r2 = lower_cell(2, scan=False)
r8u = lower_cell(8, scan=False)
r8s = lower_cell(8, scan=True)
per_layer_f = r2["flops"] - r1["flops"]
per_layer_b = r2["bytes"] - r1["bytes"]
pred_f = r1["flops"] + 7 * per_layer_f
pred_b = r1["bytes"] + 7 * per_layer_b
print("L=1 unroll:", r1)
print("L=2 unroll:", r2)
print("L=8 unroll:", r8u)
print("L=8 scan  :", r8s)
print(f"probe pred flops {pred_f:.3e} vs true {r8u['flops']:.3e} "
      f"ratio {pred_f/r8u['flops']:.4f}")
print(f"probe pred bytes {pred_b:.3e} vs true {r8u['bytes']:.3e} "
      f"ratio {pred_b/r8u['bytes']:.4f}")
print(f"scan-once check: scan flops {r8s['flops']:.3e} vs L1 {r1['flops']:.3e}")
