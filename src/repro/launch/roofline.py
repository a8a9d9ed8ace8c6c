"""Roofline extraction from the compiled dry-run artifact.

Three terms per (arch × shape × mesh), all in seconds (TPU v5e constants):

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = Σ collective_bytes_per_device / link_bw

cost_analysis() provides per-device FLOPs and bytes-accessed. Collective
bytes are NOT in cost_analysis — they are parsed from the post-SPMD
compiled HLO: we sum the OPERAND sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (per-device shapes; for
all-gather the operand is the per-device contribution, matching ring-cost
intuition within a small factor).

MODEL_FLOPS is the analytic useful-work count (6·N·D train / 2·N·D decode,
N = active params, plus the causal-attention term) — the
MODEL_FLOPS/HLO_FLOPs ratio exposes remat recompute and SVRG's intrinsic
2x gradient cost.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple


from repro.config import HardwareSpec, ModelConfig, ShapeConfig, TPU_V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[4096,1024]{1,0}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# ---------------------------------------------------------------------------
# Jaxpr-level cost model (exact loop trip counts — XLA's cost_analysis visits
# while bodies ONCE, undercounting scan-over-layers programs by ~L)
# ---------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * aval.dtype.itemsize


def _dot_flops(eqn) -> float:
    dnums = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dnums
    lhs = eqn.invars[0].aval
    m = 1
    for i, d in enumerate(lhs.shape):
        if i in lc:
            m *= d        # contraction
        elif i in lb:
            m *= d        # batch
    out = 1
    for d in eqn.outvars[0].aval.shape:
        out *= d
    k = 1
    for i in lc:
        k *= lhs.shape[i]
    return 2.0 * out * k


_RECURSE_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")
_MATERIAL_PRIMS = {
    "dot_general", "conv_general_dilated", "gather", "scatter",
    "scatter-add", "scatter_add", "dynamic_slice", "dynamic_update_slice",
    "take", "sort", "top_k", "cumsum", "concatenate",
}


def jaxpr_cost(jaxpr) -> Dict[str, float]:
    """(flops, materialized bytes) of a ClosedJaxpr/Jaxpr, with scan bodies
    multiplied by their trip count. Bytes count only "materialization
    points" (matmul/gather/scan-boundary traffic) as an HBM-traffic proxy —
    pure elementwise chains are assumed fused."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    flops = 0.0
    bytes_ = 0.0
    for eqn in jx.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            n = eqn.params["length"]
            inner = jaxpr_cost(eqn.params["jaxpr"])
            flops += n * inner["flops"]
            bytes_ += n * inner["bytes"]
            # xs/ys slicing + carry read/write per iteration
            num_carry = eqn.params["num_carry"]
            carry_b = sum(_aval_bytes(v.aval)
                          for v in eqn.invars[eqn.params["num_consts"]:
                                              eqn.params["num_consts"] + num_carry])
            xs_b = sum(_aval_bytes(v.aval)
                       for v in eqn.invars[eqn.params["num_consts"] + num_carry:])
            ys_b = sum(_aval_bytes(v.aval) for v in eqn.outvars[num_carry:])
            bytes_ += xs_b + ys_b + 2.0 * n * carry_b
            continue
        if prim == "cond":
            branches = eqn.params["branches"]
            costs = [jaxpr_cost(b) for b in branches]
            flops += max(c["flops"] for c in costs)
            bytes_ += max(c["bytes"] for c in costs)
            continue
        recursed = False
        for pname in _RECURSE_PARAMS:
            if pname in eqn.params:
                inner = jaxpr_cost(eqn.params[pname])
                flops += inner["flops"]
                bytes_ += inner["bytes"]
                recursed = True
                break
        if recursed:
            continue
        if prim == "dot_general":
            flops += _dot_flops(eqn)
            bytes_ += sum(_aval_bytes(v.aval) for v in eqn.invars) \
                + sum(_aval_bytes(v.aval) for v in eqn.outvars)
            continue
        # elementwise/reduction flop estimate: 1 flop per output element
        out_b = 0
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                n = 1
                for d in v.aval.shape:
                    n *= int(d)
                flops += n
                out_b += _aval_bytes(v.aval)
        if prim in _MATERIAL_PRIMS:
            bytes_ += out_b + sum(_aval_bytes(v.aval) for v in eqn.invars
                                  if hasattr(v.aval, "shape"))
    return {"flops": flops, "bytes": bytes_}


# ---------------------------------------------------------------------------
# Trip-count-aware collective parse of post-SPMD HLO
# ---------------------------------------------------------------------------

def _split_computations(hlo_text: str) -> Dict[str, str]:
    comps: Dict[str, List[str]] = {}
    name = None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->.*{", line)
        if m and not line.lstrip().startswith("%"):
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
            if line.strip() == "}":
                name = None
    return {k: "\n".join(v) for k, v in comps.items()}


def _while_trip_count(cond_text: str) -> int:
    """Estimate a while loop's trip count from its condition computation:
    the loop bound appears as the largest s32 constant compared against."""
    consts = [int(c) for c in re.findall(r"constant\((\d+)\)", cond_text)]
    return max(consts) if consts else 1


def collective_bytes_with_trips(hlo_text: str) -> Dict[str, float]:
    """Per-device collective bytes, multiplying ops inside while bodies by
    the loop trip count (scan-over-layers puts one all-gather per layer
    INSIDE the loop — a flat parse undercounts by ~num_layers)."""
    comps = _split_computations(hlo_text)
    # multipliers: computation -> trip multiplier (propagated through calls)
    mult: Dict[str, float] = {}

    entry = None
    for name in comps:
        if ".clone" not in name and ("main" in name or entry is None):
            pass
    # find callee edges
    def edges(text):
        out = []
        for m in re.finditer(r"while\(.*?\)[^\n]*?condition=%?([\w.\-]+)[^\n]*?body=%?([\w.\-]+)", text):
            out.append(("while", m.group(1), m.group(2)))
        for m in re.finditer(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", text):
            out.append(("call", None, m.group(1)))
        return out

    # BFS from every root (computations not referenced elsewhere)
    referenced = set()
    for text in comps.values():
        for m in re.finditer(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", text):
            referenced.add(m.group(1))
    roots = [n for n in comps if n not in referenced] or list(comps)[:1]

    for r in roots:
        mult.setdefault(r, 1.0)
    work = list(roots)
    seen = set()
    while work:
        cur = work.pop()
        if cur in seen or cur not in comps:
            continue
        seen.add(cur)
        text = comps[cur]
        base = mult.get(cur, 1.0)
        for m in re.finditer(
                r"while\([^\n]*?condition=%?([\w.\-]+)[^\n]*?body=%?([\w.\-]+)",
                text):
            cond, body = m.group(1), m.group(2)
            trips = _while_trip_count(comps.get(cond, ""))
            mult[body] = max(mult.get(body, 0.0), base * trips)
            mult[cond] = max(mult.get(cond, 0.0), base * trips)
            work += [body, cond]
        for m in re.finditer(r"(?:calls|to_apply)=%?([\w.\-]+)", text):
            callee = m.group(1)
            mult[callee] = max(mult.get(callee, 0.0), base)
            work.append(callee)

    out = {k: 0.0 for k in _COLLECTIVES}
    out["count"] = 0.0
    for name, text in comps.items():
        local = parse_collective_bytes(text)
        f = mult.get(name, 1.0)
        for k in _COLLECTIVES:
            out[k] += local[k] * f
        out["count"] += local["count"] * f
    return out


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+(all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start|-done)?\(", line)
        if not m:
            continue
        kind = m.group(2)
        if "-done" in line.split("(")[0]:
            continue          # count the -start, skip the -done
        # operand shapes: everything inside the call parens
        call = line.split("(", 1)[1]
        operands = call.rsplit(")", 1)[0]
        # operand list references %names — their shapes are not on this line;
        # use the OUTPUT shape as the proxy for a-r/r-s/a2a/c-p (same size),
        # and for all-gather divide by the group size parsed from
        # replica_groups (operand = output / group).
        out_bytes = _shape_bytes(m.group(1))
        if kind == "all-gather":
            g = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
            if g:
                out_bytes //= max(1, int(g.group(2)))
            else:
                g2 = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
                if g2:
                    out_bytes //= max(1, len(g2.group(1).split(",")))
        out[kind] += out_bytes
        out["count"] += 1
    return out


# ---------------------------------------------------------------------------
# Analytic useful-work FLOPs
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, defs) -> Tuple[int, int]:
    """(total, active) param counts from the ParamDef tree."""
    from repro.sharding.rules import is_param_def
    import jax

    total = 0
    active = 0
    frac = 1.0
    if cfg.num_experts > 0:
        frac = cfg.experts_per_token / cfg.num_experts

    def visit(path, d):
        nonlocal total, active
        n = 1
        for s in d.shape:
            n *= s
        total += n
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        if "moe" in key and "shared" not in key and "router" not in key:
            active += int(n * frac)
        else:
            active += n

    for path, d in jax.tree_util.tree_flatten_with_path(
            defs, is_leaf=is_param_def)[0]:
        visit(path, d)
    return total, active


def attention_flops(cfg: ModelConfig, S: int, B: int, decode: bool) -> float:
    """QK^T + AV flops (fwd). Window-aware; causal halves the full case."""
    if cfg.family == "ssm":
        return 0.0
    d_attn = cfg.num_heads * cfg.head_dim
    if cfg.family == "hybrid":
        G = cfg.num_layers // 3
        layers = G            # only attn layers
        window = min(cfg.local_window, S)
        keys = window if decode else window  # local
        eff = S * keys if not decode else keys
        return 4.0 * B * layers * d_attn * eff
    layers = cfg.num_layers
    if decode:
        keys = S
        per_layer = 4.0 * B * d_attn * keys      # one query
    else:
        if cfg.attn_pattern == "local_global":
            n_global = layers // cfg.global_every
            n_local = layers - n_global
            w = min(cfg.local_window, S)
            per_global = 4.0 * B * d_attn * S * S * 0.5
            per_local = 4.0 * B * d_attn * S * w
            return n_global * per_global + n_local * per_local
        per_layer = 4.0 * B * d_attn * S * S * 0.5
    total = layers * per_layer
    if cfg.family == "encdec" and not decode:
        total += cfg.encoder_layers * 4.0 * B * d_attn * cfg.encoder_seq ** 2
        total += layers * 4.0 * B * d_attn * S * cfg.encoder_seq
    if cfg.family == "vlm":
        n_cross = layers // 5
        total += n_cross * 4.0 * B * d_attn * (1 if decode else S) * cfg.num_image_tokens
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig, defs) -> float:
    total, active = count_params(cfg, defs)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens + 3.0 * attention_flops(
            cfg, shape.seq_len, shape.global_batch, decode=False)
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens + attention_flops(
            cfg, shape.seq_len, shape.global_batch, decode=False)
    # decode: one token per sequence
    return 2.0 * active * shape.global_batch + attention_flops(
        cfg, shape.seq_len, shape.global_batch, decode=True)


# ---------------------------------------------------------------------------
# Fused sweep-epoch megakernel: analytic intensity headroom
# ---------------------------------------------------------------------------

def sweep_epoch_roofline(*, rows: int, dim: int, total: int, epochs: int,
                         buf_len: int, hw: HardwareSpec = TPU_V5E,
                         dtype_bytes: int = 4) -> Dict:
    """Arithmetic-intensity headroom of the fused sweep-epoch megakernel
    over the vmap engine for one (rows × epochs × M̃) group.

    Both paths run the same FLOPs — per update, two component gradients
    (~2·2·dim each for the dot + axpy shape shared by the repo's
    objectives) plus the control-variate combine (~3·dim), ≈ 11·dim. What
    differs is HBM traffic per update:

      * vmap: the XLA scan carry — the iterate ``w``, the PRNG key + loss
        slot, and the ``buf_len``-deep delay ring — is read AND written
        through HBM every update, so bytes/update ≈ 2·(buf_len + 2)·dim·b
        plus the sampled data row.
      * fused: the carry lives in VMEM for the whole (row × epoch); only
        the sampled data row moves per update, with the per-row boundary
        I/O (w0 in, w_fin + history out) amortized over epochs·M̃ updates.

    The intensity ratio is the roofline-predicted speedup bound in the
    memory-bound regime (the AsySVRG inner loop's regime: intensity ~2
    flops/byte << every listed hw's ridge). Returns both paths' terms so
    benchmarks can log predicted vs measured side by side.
    """
    updates = float(rows) * epochs * total
    flops_per_update = 11.0 * dim
    flops = updates * flops_per_update
    row_bytes = dim * dtype_bytes                       # sampled data row
    carry_bytes = 2.0 * (buf_len + 2) * dim * dtype_bytes
    boundary = rows * dtype_bytes * (2.0 * dim + epochs + 1)

    out: Dict = {"rows": rows, "dim": dim, "total": total, "epochs": epochs,
                 "buf_len": buf_len, "flops": flops}
    for path, bytes_ in (("vmap", updates * (row_bytes + carry_bytes)
                          + boundary),
                         ("fused", updates * row_bytes + boundary)):
        t_compute = flops / hw.peak_flops_bf16
        t_memory = bytes_ / hw.hbm_bandwidth
        out[path] = {
            "bytes": bytes_,
            "intensity_flops_per_byte": flops / bytes_,
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "step_lower_bound_s": max(t_compute, t_memory),
            "dominant": "compute" if t_compute >= t_memory else "memory",
        }
    out["intensity_headroom"] = (
        out["fused"]["intensity_flops_per_byte"]
        / out["vmap"]["intensity_flops_per_byte"])
    out["predicted_speedup"] = (out["vmap"]["step_lower_bound_s"]
                                / out["fused"]["step_lower_bound_s"])
    return out


def attained_fraction(*, rows: int, dim: int, total: int, epochs: int,
                      buf_len: int, fused: bool, wall_s: float,
                      hw: HardwareSpec = TPU_V5E) -> Dict:
    """Attained-vs-roofline fraction for one MEASURED group dispatch.

    Selects the engine path (vmap or fused megakernel) of
    :func:`sweep_epoch_roofline` and divides its step lower bound by the
    measured wall time — a per-group "how close to the hardware are
    we" number. On a backend other than ``hw``
    (e.g. the CPU CI container vs the TPU_V5E default) the fraction is a
    cross-hardware comparison, not a utilization: still monotone in
    dispatch speed, so regressions show, but only meaningful in absolute
    terms when ``hw`` matches the machine.
    """
    rf = sweep_epoch_roofline(rows=rows, dim=dim, total=total,
                              epochs=epochs, buf_len=buf_len, hw=hw)
    path = rf["fused" if fused else "vmap"]
    return {
        "roofline_s": path["step_lower_bound_s"],
        "attained_frac": (path["step_lower_bound_s"] / wall_s
                          if wall_s > 0 else 0.0),
        "flops": rf["flops"],
        "bytes": path["bytes"],
        "dominant": path["dominant"],
    }


# ---------------------------------------------------------------------------
# Terms
# ---------------------------------------------------------------------------

def roofline_terms(record: Dict, hw: HardwareSpec = TPU_V5E) -> Dict:
    """record: one dry-run JSON.

    Sources, in order of trust:
      * flops/bytes: the jaxpr cost model (exact scan trip counts), global,
        divided by chip count. Falls back to cost_analysis (which visits
        while bodies once — undercounts scan programs by ~num_layers).
      * collectives: trip-count-multiplied HLO parse (per-device shapes).
    """
    chips = record["num_devices"]
    jc = record.get("jaxpr_cost")
    if jc:
        flops = jc["flops"] / chips
        bytes_acc = jc["bytes"] / chips
        source = "jaxpr"
    else:
        flops = record["cost"].get("flops", 0.0)
        bytes_acc = record["cost"].get("bytes accessed", 0.0)
        source = "hlo_cost_analysis"
    coll = record.get("collectives_trips") or record["collectives"]
    coll_bytes = sum(coll.get(k, 0) for k in _COLLECTIVES)
    t_compute = flops / hw.peak_flops_bf16
    t_memory = bytes_acc / hw.hbm_bandwidth
    t_coll = coll_bytes / hw.ici_bandwidth
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))[1]
    bound = max(t_compute, t_memory, t_coll)
    mf = record.get("model_flops", 0.0)
    hlo_total = flops * chips
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "step_lower_bound_s": bound,
        "model_flops": mf,
        "hlo_flops_total": hlo_total,
        "useful_ratio": (mf / hlo_total) if hlo_total else 0.0,
        "mfu_upper_bound": (mf / (chips * hw.peak_flops_bf16)) / bound
        if bound else 0.0,
        "cost_source": source,
    }
