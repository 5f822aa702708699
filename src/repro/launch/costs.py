"""Cost extraction for the roofline analysis.

Two independent problems are solved here:

1.  **While-loop undercounting.**  XLA's ``cost_analysis()`` counts a
    ``while`` body *once*, not ×trip-count — with scan-over-layers (and
    scan-over-experts / chunk scans / microbatch scans) the reported FLOPs
    for a 48-layer model equal those of a 1-layer model (verified
    empirically, see EXPERIMENTS.md §Dry-run).  We therefore lower a **cost
    probe**: the *same* step with every scan unrolled
    (``cfg.scan_layers=False``) at small depth knobs, and extrapolate the
    exactly-linear depth dependence:

        dense/moe/ssm/vlm :  F(L)      = F(1) + (L-1)·[F(2) - F(1)]
        hybrid (zamba2)   :  F(L)      = F(1) + (L-1)·ΔM + (ceil(L/ae)-1)·ΔA
        encdec            :  F(Le, Ld) = F(1,1) + (Le-1)·ΔE + (Ld-1)·ΔD

    MoE expert loops and attention/SSM chunk scans are unrolled *exactly*
    in the probe (no modeling).  Microbatch count does not change total
    step cost (same tokens), so probes run with ``microbatches=1``.

2.  **Collective bytes.**  Not present in ``cost_analysis()``; parsed from
    the optimized HLO of the probe compiles (fully unrolled → no trip-count
    logic).  We build a symbol table of instruction shapes and, for each
    ``all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute`` (including ``-start`` async forms), record operand
    bytes (per spec) and modeled link-bytes (all-reduce 2×(n-1)/n,
    others (n-1)/n of the payload).
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import Counter
from typing import Any, Dict, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"^\(?(?P<shapes>[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collectives(hlo: str, n_devices_per_group: Optional[int] = None
                      ) -> Dict[str, Any]:
    """Sum collective payload bytes from optimized HLO text.

    Payload convention: per-device *output* bytes of each collective (for
    tuple-shaped ops, sum of tuple elements).  Returns operand-bytes total,
    modeled link-bytes total, and per-op-kind counts/bytes.
    """
    counts: Counter = Counter()
    bytes_by_kind: Counter = Counter()
    total_payload = 0
    total_link = 0.0
    for line in hlo.splitlines():
        parts = line.split(" = ", 1)
        if len(parts) != 2:
            continue
        m = _COLL_RE.match(parts[1].strip())
        if m is None or m.group("suffix") == "-done":
            continue
        kind = m.group("op")
        shapes = _SHAPE_RE.findall(m.group("shapes"))
        payload = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        if m.group("suffix") == "-start":
            payload //= 2  # async start ops carry (operand, result) tuples
        counts[kind] += 1
        bytes_by_kind[kind] += payload
        total_payload += payload
        # modeled bytes crossing links per device
        n = n_devices_per_group or 2
        frac = (n - 1) / n
        if kind == "all-reduce":
            total_link += 2 * payload * frac
        else:
            total_link += payload * frac
    return {
        "counts": dict(counts),
        "bytes_by_kind": dict(bytes_by_kind),
        "payload_bytes": int(total_payload),
        "link_bytes": float(total_link),
    }


# ---------------------------------------------------------------------------
# Probe
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellCosts:
    flops: float               # whole-step HLO FLOPs (all devices combined)
    bytes: float               # whole-step HBM bytes accessed
    coll_payload: float        # per-device collective payload bytes
    coll_link: float           # per-device modeled link bytes
    coll_counts: Dict[str, int]
    probe_points: Dict[str, Any]


def _probe_cfg(cfg, **kw):
    return cfg.replace(scan_layers=False, microbatches=1, **kw)


def _lower_one(cfg, shape, mesh, hp=None) -> Dict[str, Any]:
    import jax
    from repro.distributed import sharding as shd
    from repro.training.steps import build_cell
    cell = build_cell(cfg, shape, mesh, hp)
    with shd.active_mesh(mesh), shd.activation_rules(shd.make_rules(cfg, mesh)):
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          out_shardings=cell.out_shardings).lower(*cell.args)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = parse_collectives(hlo, n_devices_per_group=mesh.shape.get("model", 2))
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": colls,
    }


def _lower_costs(cfg, shape, mesh, hp=None) -> Dict[str, Any]:
    """Lower+compile one probe point; return flops/bytes/collectives.

    FLOPs come from an f32 compile: XLA *CPU* legalizes bf16 through f32
    convert chains whose flop count grows O(L²) with unrolled depth — a
    host-backend artifact absent on native-bf16 TPU (verified: f32 compiles
    are exactly depth-linear, and dot counts match between dtypes).  Bytes
    and collective payloads keep the model dtype (traffic is dtype-real).
    """
    base = _lower_one(cfg, shape, mesh, hp)
    if cfg.dtype != "float32":
        f32 = _lower_one(cfg.replace(dtype="float32"), shape, mesh, hp)
        base = dict(base, flops=f32["flops"])
    return base


def _combine(base, deltas_with_mult):
    """base + sum(mult · delta) for flops/bytes/collective fields."""
    out = dict(flops=base["flops"], bytes=base["bytes"],
               payload=base["coll"]["payload_bytes"],
               link=base["coll"]["link_bytes"],
               counts=Counter(base["coll"]["counts"]))
    for mult, (hi, lo) in deltas_with_mult:
        out["flops"] += mult * (hi["flops"] - lo["flops"])
        out["bytes"] += mult * (hi["bytes"] - lo["bytes"])
        out["payload"] += mult * (hi["coll"]["payload_bytes"]
                                  - lo["coll"]["payload_bytes"])
        out["link"] += mult * (hi["coll"]["link_bytes"]
                               - lo["coll"]["link_bytes"])
        dc = Counter(hi["coll"]["counts"])
        dc.subtract(lo["coll"]["counts"])
        for kk, vv in dc.items():
            out["counts"][kk] += int(round(mult * vv))
    return out


MAX_UNROLL_CHUNKS = 64


def _chunk_knob(cfg, shape):
    """(chunk_len, n_chunks_real) for sequence-chunked families, else None."""
    if shape.kind == "decode":
        return None
    if cfg.family == "ssm":
        c = cfg.rwkv.chunk
    elif cfg.family == "hybrid":
        c = cfg.ssm.chunk
    else:
        return None
    if shape.seq_len % c:
        return None
    return c, shape.seq_len // c


def probe_costs(cfg, shape, mesh, hp=None) -> CellCosts:
    """Depth-probe + linear extrapolation (see module docstring).

    Sequence-chunked families (Mamba2/RWKV6) at long S would unroll
    hundreds of chunk bodies in the probe; instead the probe runs at three
    small chunk counts nc ∈ {2,4,8} and fits F(nc) = c0 + c1·nc + c2·nc²
    per depth point (the quadratic term captures attention-over-cache in
    the hybrid's shared attention), then evaluates at the real nc."""
    knob = _chunk_knob(cfg, shape)
    if knob is not None and knob[1] > MAX_UNROLL_CHUNKS:
        return _probe_costs_chunk_extrapolated(cfg, shape, mesh, hp, knob)
    return _probe_costs_depth(cfg, shape, mesh, hp)


def _probe_costs_chunk_extrapolated(cfg, shape, mesh, hp, knob) -> CellCosts:
    import dataclasses as _dc
    chunk, nc_real = knob
    ncs = (2, 4, 8)
    sub = []
    for nc in ncs:
        s2 = _dc.replace(shape, name=f"{shape.name}~nc{nc}",
                         seq_len=nc * chunk)
        sub.append(_probe_costs_depth(cfg, s2, mesh, hp))

    def quad(vals):
        c = np.polyfit(np.array(ncs, float), np.array(vals, float), 2)
        return float(np.polyval(c, nc_real))

    keys = Counter()
    for s in sub:
        keys.update(s.coll_counts)
    counts = {k: int(round(quad([s.coll_counts.get(k, 0) for s in sub])))
              for k in keys}
    return CellCosts(
        flops=quad([s.flops for s in sub]),
        bytes=quad([s.bytes for s in sub]),
        coll_payload=quad([s.coll_payload for s in sub]),
        coll_link=quad([s.coll_link for s in sub]),
        coll_counts=counts,
        probe_points={f"nc{nc}": s.probe_points for nc, s in zip(ncs, sub)})


def _probe_costs_depth(cfg, shape, mesh, hp=None) -> CellCosts:
    fam = cfg.family
    L = cfg.n_layers
    pts: Dict[str, Any] = {}

    if fam == "hybrid":
        ae = cfg.hybrid.attn_every
        c1 = _lower_costs(_probe_cfg(cfg, n_layers=1), shape, mesh, hp)
        c2 = _lower_costs(_probe_cfg(cfg, n_layers=2), shape, mesh, hp)
        ca = _lower_costs(_probe_cfg(cfg, n_layers=ae + 1), shape, mesh, hp)
        pts = {"L1": c1, "L2": c2, f"L{ae+1}": ca}
        # ΔM = c2-c1 (extra mamba layer); attn block delta:
        # ca = c1 + ae·ΔM + ΔA  =>  ΔA = ca - c1 - ae·ΔM
        n_groups = math.ceil(L / ae)
        dm = (c2, c1)
        # synthesize ΔA pair
        da_hi = {"flops": ca["flops"] - ae * (c2["flops"] - c1["flops"]),
                 "bytes": ca["bytes"] - ae * (c2["bytes"] - c1["bytes"]),
                 "coll": {"payload_bytes":
                          ca["coll"]["payload_bytes"] - ae * (
                              c2["coll"]["payload_bytes"]
                              - c1["coll"]["payload_bytes"]),
                          "link_bytes":
                          ca["coll"]["link_bytes"] - ae * (
                              c2["coll"]["link_bytes"]
                              - c1["coll"]["link_bytes"]),
                          "counts": {}}}
        tot = _combine(c1, [(L - 1, dm), (n_groups - 1, (da_hi, c1))])
    elif fam == "encdec":
        import repro.configs.base as cb
        e1d1 = _probe_cfg(cfg, n_layers=1,
                          encdec=cb.EncDecConfig(1, cfg.encdec.encoder_frac))
        e2d1 = _probe_cfg(cfg, n_layers=1,
                          encdec=cb.EncDecConfig(2, cfg.encdec.encoder_frac))
        e1d2 = _probe_cfg(cfg, n_layers=2,
                          encdec=cb.EncDecConfig(1, cfg.encdec.encoder_frac))
        c11 = _lower_costs(e1d1, shape, mesh, hp)
        c21 = _lower_costs(e2d1, shape, mesh, hp)
        c12 = _lower_costs(e1d2, shape, mesh, hp)
        pts = {"e1d1": c11, "e2d1": c21, "e1d2": c12}
        Le = cfg.encdec.n_encoder_layers
        tot = _combine(c11, [(Le - 1, (c21, c11)), (L - 1, (c12, c11))])
    else:
        c1 = _lower_costs(_probe_cfg(cfg, n_layers=1), shape, mesh, hp)
        c2 = _lower_costs(_probe_cfg(cfg, n_layers=2), shape, mesh, hp)
        pts = {"L1": c1, "L2": c2}
        tot = _combine(c1, [(L - 1, (c2, c1))])

    return CellCosts(flops=tot["flops"], bytes=tot["bytes"],
                     coll_payload=tot["payload"], coll_link=tot["link"],
                     coll_counts=dict(tot["counts"]), probe_points=pts)


# ---------------------------------------------------------------------------
# Analytic model FLOPs (6·N·D convention)
# ---------------------------------------------------------------------------

def matmul_param_count(cfg) -> Tuple[float, float]:
    """(dense-equivalent matmul params, active matmul params).

    Counts every parameter that participates in a matmul (incl. the LM
    head, excl. the token-embedding gather).  For MoE the active count
    scales expert FFN params by top_k/E.
    """
    D, H, KV, Hd, F, V, L = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.resolved_head_dim, cfg.d_ff, cfg.vocab,
                             cfg.n_layers)
    head = D * V
    if cfg.family in ("dense", "vlm"):
        attn = D * H * Hd + 2 * D * KV * Hd + H * Hd * D
        ffn = 3 * D * F
        tot = L * (attn + ffn) + head
        if cfg.family == "vlm":
            tot += cfg.vlm.patch_dim * D
        return tot, tot
    if cfg.family == "moe":
        attn = D * H * Hd + 2 * D * KV * Hd + H * Hd * D
        E, k = cfg.moe.n_experts, cfg.moe.top_k
        ffn_all = 3 * D * F * E
        gate = D * E
        tot = L * (attn + ffn_all + gate) + head
        act = L * (attn + 3 * D * F * k + gate) + head
        return tot, act
    if cfg.family == "ssm":  # rwkv6
        Hh, K = D // cfg.rwkv.head_dim, cfg.rwkv.head_dim
        tmix = 4 * D * D + D * cfg.rwkv.decay_lora + cfg.rwkv.decay_lora * D + D * D
        cmix = 2 * D * F + D * D
        tot = L * (tmix + cmix) + head
        return tot, tot
    if cfg.family == "hybrid":
        d_in = cfg.ssm.expand * D
        Hs = d_in // cfg.ssm.head_dim
        N = cfg.ssm.d_state
        mamba = 2 * D * d_in + 2 * D * N + D * Hs + d_in * D
        attn = D * H * Hd + 2 * D * KV * Hd + H * Hd * D + 3 * D * F
        napp = math.ceil(L / cfg.hybrid.attn_every)
        tot = L * mamba + napp * attn + head
        return tot, tot
    if cfg.family == "encdec":
        attn = D * H * Hd + 2 * D * KV * Hd + H * Hd * D
        ffn = 3 * D * F
        enc = cfg.encdec.n_encoder_layers * (attn + ffn)
        dec = L * (2 * attn + ffn)
        tot = enc + dec + head + D * D
        return tot, tot
    raise ValueError(cfg.family)


def model_flops(cfg, shape) -> float:
    """6·N_active·T (+ attention context term) for the given cell."""
    _, act = matmul_param_count(cfg)
    B, S = shape.global_batch, shape.seq_len
    Hd = cfg.resolved_head_dim

    def attn_ctx_flops(n_layers, heads, q_tokens, ctx, causal):
        # qk^T + att·v = 2 · 2 · q·ctx·heads·Hd  (×0.5 if causal averaged)
        f = 4 * q_tokens * ctx * heads * Hd
        return f * (0.5 if causal else 1.0)

    if shape.kind == "train":
        if cfg.family == "encdec":
            fr = int(S * cfg.encdec.encoder_frac)
            dec = S - fr
            # separate enc/dec token counts
            attn = (cfg.d_model * cfg.n_heads * Hd + 2 * cfg.d_model
                    * cfg.n_kv_heads * Hd + cfg.n_heads * Hd * cfg.d_model)
            ffn = 3 * cfg.d_model * cfg.d_ff
            enc_p = cfg.encdec.n_encoder_layers * (attn + ffn)
            dec_p = cfg.n_layers * (2 * attn + ffn)
            head = cfg.d_model * cfg.vocab
            f = 6 * (enc_p * B * fr + (dec_p + head) * B * dec)
            f += 3 * attn_ctx_flops(cfg.encdec.n_encoder_layers, cfg.n_heads,
                                    B * fr, fr, False)
            f += 3 * attn_ctx_flops(cfg.n_layers, cfg.n_heads, B * dec, dec, True)
            f += 3 * attn_ctx_flops(cfg.n_layers, cfg.n_heads, B * dec, fr, False)
            return f
        T = B * S
        f = 6.0 * act * T
        if cfg.family in ("dense", "vlm", "moe"):
            f += 3 * cfg.n_layers * attn_ctx_flops(1, cfg.n_heads, T, S, True)
        elif cfg.family == "hybrid":
            napp = math.ceil(cfg.n_layers / cfg.hybrid.attn_every)
            f += 3 * napp * attn_ctx_flops(1, cfg.n_heads, T, S, True)
        return f

    # inference: 2·N_active per token (+ attention over context)
    q_tokens = B * (S if shape.kind == "prefill" else 1)
    f = 2.0 * act * q_tokens
    ctx = S
    causal = shape.kind == "prefill"
    if cfg.family in ("dense", "vlm", "moe"):
        f += cfg.n_layers * attn_ctx_flops(1, cfg.n_heads, q_tokens, ctx, causal)
    elif cfg.family == "hybrid":
        napp = math.ceil(cfg.n_layers / cfg.hybrid.attn_every)
        f += napp * attn_ctx_flops(1, cfg.n_heads, q_tokens, ctx, causal)
    elif cfg.family == "encdec":
        fr = int(S * cfg.encdec.encoder_frac)
        dec = S - fr
        if shape.kind == "prefill":
            f = 2.0 * act * B * S  # enc on frames + dec prefill, roughly
        f += cfg.n_layers * attn_ctx_flops(1, cfg.n_heads, q_tokens, fr, False)
        f += cfg.n_layers * attn_ctx_flops(1, cfg.n_heads, q_tokens, dec, causal)
    return f
