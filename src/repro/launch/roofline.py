"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch x shape x mesh), in seconds:

  compute    = HLO_FLOPs / (chips x peak)          [cost_analysis, per-device,
                                                    so chips cancels: /peak]
  memory     = HLO_bytes / (chips x HBM_bw)        [same]
  collective = collective_bytes / (chips x link_bw)

cost_analysis() on an SPMD-partitioned executable reports the PER-DEVICE
program (verified empirically: a (1024,1024,1024) matmul on 16 devices
reports 2MNK/16 flops), so the per-chip time is value/peak directly.
collective_bytes is parsed from the compiled HLO text: the summed operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (also per-device).

Hardware model (TPU v5e target): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.

CPU-backend caveat (DESIGN.md §8): HLO_bytes reflects the CPU lowering's
fusion decisions, which differ from TPU's in the tail ops; flops and
collective bytes are partitioning-determined and transfer.
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

# Bits per element for every HLO primitive type.  PRED counts as one BIT —
# the historical convention of this model (and the lower bound a packed
# mask costs); s4/u4/s2/u2 are bit-packed, so byte sizes round up per
# array, not per element; c64 is two f32s.
_DTYPE_BITS = {
    "pred": 1,
    "s2": 2, "u2": 2, "s4": 4, "u4": 4,
    "s8": 8, "u8": 8,
    "f8e3m4": 8, "f8e4m3": 8, "f8e4m3fn": 8, "f8e4m3b11fnuz": 8,
    "f8e4m3fnuz": 8, "f8e5m2": 8, "f8e5m2fnuz": 8, "f8e8m0fnu": 8,
    "f4e2m1fn": 4,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32, "tf32": 32,
    "s64": 64, "u64": 64, "f64": 64,
    "c64": 64, "c128": 128,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+"
                       r"([\w\-]+)\((.*)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def bytes_of_type(type_str: str) -> int:
    """Total bytes of all dtype[dims] shapes in a (possibly tuple) type.

    Exact over the full HLO element-type table (raises on an element type
    it does not know rather than silently undercounting — a new XLA dtype
    must be added to ``_DTYPE_BITS`` with its real width).
    """
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BITS:
            raise ValueError(
                f"unknown HLO element type {dtype!r} in {type_str!r} — "
                f"add its width to repro.launch.roofline._DTYPE_BITS")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += (n * _DTYPE_BITS[dtype] + 7) // 8
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective family (operand sizes)."""
    sizes: dict[str, int] = {}
    per_op = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, op, operands = m.groups()
        sizes[name] = bytes_of_type(rtype)
        base = op
        for c in _COLLECTIVES:
            if base == c or base.startswith(c + "-start") or \
                    base.startswith(c + "."):
                opnames = _OPERAND_RE.findall(operands)
                ob = sum(sizes.get(o, 0) for o in opnames)
                if ob == 0:          # fallback: result size
                    ob = sizes[name]
                per_op[c] += ob
                counts[c] += 1
                break
    per_op["total"] = sum(per_op[c] for c in _COLLECTIVES)
    per_op["counts"] = counts
    return per_op


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_chip: float
    hlo_bytes_per_chip: float
    collective_bytes_per_chip: float
    collective_breakdown: dict
    model_flops_global: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    useful_flops_ratio: float     # MODEL_FLOPS / (chips * HLO_FLOPs)
    memory_stats: dict

    def to_dict(self):
        return dataclasses.asdict(self)


def cost_analysis_dict(compiled) -> dict:
    """Normalize compiled.cost_analysis(): newer jax returns a flat dict,
    older versions a one-element list of per-program dicts."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


def analyze(arch: str, shape: str, mesh_name: str, chips: int, compiled,
            model_flops_global: float, override: dict | None = None
            ) -> RooflineReport:
    ca = cost_analysis_dict(compiled)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    if override is not None:
        # Trip-count-corrected values (see dryrun.cost_extrapolated).
        flops = float(override["flops"])
        byts = float(override["bytes"])
        coll = dict(coll, total=float(override["coll"]))
    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll["total"] / ICI_BW
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]
    ms = compiled.memory_analysis()
    mem_stats = dict(
        argument_bytes=int(getattr(ms, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(ms, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(ms, "temp_size_in_bytes", 0)),
        code_bytes=int(getattr(ms, "generated_code_size_in_bytes", 0)),
        alias_bytes=int(getattr(ms, "alias_size_in_bytes", 0)),
    )
    useful = model_flops_global / max(flops * chips, 1.0)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops_per_chip=flops, hlo_bytes_per_chip=byts,
        collective_bytes_per_chip=float(coll["total"]),
        collective_breakdown={k: v for k, v in coll.items() if k != "counts"},
        model_flops_global=model_flops_global,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, useful_flops_ratio=useful, memory_stats=mem_stats)


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS (the "useful work" yardstick)
# ---------------------------------------------------------------------------


def lm_param_counts(cfg) -> dict:
    """Analytic parameter counts (total and active-per-token)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    per_layer_attn = d * hd * (h + 2 * kv) + h * hd * d
    mlp_dense = d * cfg.d_ff * (3 if cfg.mlp_kind == "swiglu" else 2)
    total = 0
    active = 0
    from ..models.transformer import block_spec, layer_counts
    spec = block_spec(cfg)
    nblocks, tail = layer_counts(cfg)
    seq = [spec[i % len(spec)] for i in range(cfg.num_layers)]
    for kind, use_moe in seq:
        if kind in ("attn", "swa", "local"):
            total += per_layer_attn
            active += per_layer_attn
            if use_moe:
                expert = mlp_dense
                total += cfg.num_experts * expert + d * cfg.num_experts
                active += cfg.experts_per_token * expert
                if cfg.moe_shared_expert:
                    total += expert
                    active += expert
            else:
                total += mlp_dense
                active += mlp_dense
        elif kind == "ssd":
            d_in = cfg.ssm_expand * d
            heads = d_in // cfg.ssm_head_dim
            proj = d * (2 * d_in + 2 * cfg.ssm_groups * cfg.ssm_state + heads)
            ssm = proj + d_in * d
            total += ssm
            active += ssm
        elif kind == "rglru":
            lw = cfg.lru_width or d
            rec = 2 * d * lw + 2 * lw * lw + lw * d
            total += rec + mlp_dense
            active += rec + mlp_dense
    embed = cfg.vocab_size * d
    total += embed if cfg.tie_embeddings else 2 * embed
    active += embed if cfg.tie_embeddings else 2 * embed
    return dict(total=total, active=active)


def lm_model_flops(cfg, shape) -> float:
    """Global useful flops for one step of the given shape.

    train: 6 * N_active * tokens  (fwd 2N + bwd 4N)
    prefill: 2 * N_active * tokens + attention term
    decode: 2 * N_active * batch + attention-over-cache term
    """
    counts = lm_param_counts(cfg)
    n_act = counts["active"]
    b, s = shape.global_batch, shape.seq_len
    hd = cfg.resolved_head_dim
    attn_layers = sum(1 for k in
                      (cfg.layer_pattern[i % len(cfg.layer_pattern)]
                       for i in range(cfg.num_layers))
                      if k in ("attn", "swa", "local"))
    if shape.kind == "train":
        flops = 6.0 * n_act * b * s
        eff_s = min(s, cfg.window) if cfg.window else s
        flops += 3 * 2 * 2 * b * s * eff_s * cfg.num_heads * hd * attn_layers / 2
        return flops
    if shape.kind == "prefill":
        flops = 2.0 * n_act * b * s
        eff_s = min(s, cfg.window) if cfg.window else s
        flops += 2 * 2 * b * s * eff_s * cfg.num_heads * hd * attn_layers / 2
        return flops
    # decode: one token against a seq_len cache
    flops = 2.0 * n_act * b
    eff_s = min(s, cfg.window) if cfg.window else s
    flops += 2 * 2 * b * eff_s * cfg.num_heads * hd * attn_layers
    return flops


def tlr_pair_update_stats(n_tiles: int, super_panels: int = 1,
                          n_shards: int = 1) -> dict:
    """Closed-form GEMM+recompress *pair-update* counts for one TLR
    factorization, by batching form (the §Perf overcompute model the
    dry-run prints next to the measured HLO flops).

      live    — pair tasks the exact triangle needs: sum_k C(T-1-k, 2)
                = C(T, 3) (only i > j > k tiles are live at step k).
      masked  — the masked full-grid batch recompresses every (T', T') slot
                of the live slice each step: the paper-faithful baseline,
                ~6x live at S = 1.
      pair    — the block-cyclic pair batch: each step recompresses only
                the static live-pair window of every shard
                (block_cyclic.window_schedule), at most 2x live + 2 S a step.

    ``super_panels = S > 1`` shrinks the live slice every outer step for
    both forms.  Counts are whole-factorization task counts (multiply by
    the per-task recompress cost for flops).
    """
    from ..distribution.block_cyclic import pair_layout, window_schedule

    T, S = n_tiles, max(super_panels, 1)
    assert T % S == 0, (T, S)
    chunk = T // S
    live = T * (T - 1) * (T - 2) // 6
    masked = pair = 0
    for s in range(S):
        ts = T - s * chunk                       # live slice width
        steps = chunk - 1 if s == S - 1 else chunk
        masked += steps * ts * ts
        pair += window_schedule(pair_layout(ts, n_shards), steps).computed
    return dict(
        live_updates=live, masked_updates=masked, pair_updates=pair,
        masked_overcompute=masked / max(live, 1),
        pair_overcompute=pair / max(live, 1),
        pair_vs_masked=masked / max(pair, 1))


def tlr_recompress_temp_model(n_tiles: int, tile_size: int, kmax: int,
                              n_shards: int = 1, itemsize: int = 4) -> dict:
    """Closed-form per-device working set of the GEMM-phase recompress batch
    (the QR/QR + core-SVD workspace the dry-run's factorize temp is made of).

    Each live pair slot holds the (nb, 2k) concat pair + its two Q factors,
    the (2k, 2k) R/R^T/core triangle, and the core SVD outputs.  Under plain
    GSPMD the batched QR/SVD has no partitioning rule, so the whole padded
    pair batch is *replicated* per device (``replicated_bytes``);
    ``distribution.pair_qr.sharded_recompress`` runs it under shard_map over
    the pair axis, so each device holds only padded/S slots
    (``sharded_bytes`` — the O(pairs/S) scaling the ROADMAP item asks for).
    """
    assert n_shards >= 1
    pairs = n_tiles * (n_tiles - 1) // 2
    padded = -(-pairs // n_shards) * n_shards if pairs else 0
    nb, k2 = tile_size, 2 * kmax
    per_pair = (4 * nb * k2          # U/V concats + their Q factors
                + 3 * k2 * k2        # R_u, R_v, core
                + 2 * k2 * k2 + k2   # core SVD U, V^T, singular values
                ) * itemsize
    return dict(pairs=pairs, padded_pairs=padded, per_pair_bytes=per_pair,
                replicated_bytes=padded * per_pair,
                sharded_bytes=(padded // n_shards) * per_pair,
                shrink=float(n_shards))


def tlr_compress_temp_model(n_tiles: int, tile_size: int, kmax: int,
                            col_block: int = 1, n_shards: int = 1,
                            itemsize: int = 4) -> dict:
    """Closed-form per-device working set of the compress-phase truncation
    SVD (one fori step of dist_compress_tiles) by placement.

    Each tile needs its (nb, nb) input, the SVD outputs U/V^T/s, and the
    truncated (nb, kmax) factors.  Under plain GSPMD the batched
    jnp.linalg.svd has no partitioning rule, so the whole column group —
    the (m, cb*nb) GEN panel plus cb*T tiles of SVD workspace — replicates
    on every device (``replicated_bytes``).  The sharded form
    (core.dist_tlr._compress_tiles_pair_sharded) walks each device's own
    block-cyclic slots *slot-major* in steps of cb*ceil((T-1)/S) tiles
    (``sharded_bytes`` per step) — the O(tiles/S) scaling the ROADMAP
    item asks for — and over the full sweep generates exactly its
    ``pairs_per_shard ~ T(T-1)/(2S)`` owned tiles (``gen_tiles_owned``).
    The former per-column sweep generated ``T*ceil((T-1)/S)`` candidate
    tiles per device (``gen_tiles_candidate``) — almost all masked
    sentinels once S >> T-1; ``gen_shrink`` is the GEN-work drop the
    slot-major sweep buys.
    """
    assert n_shards >= 1
    T, nb, cb = n_tiles, tile_size, col_block
    m = T * nb
    per_tile = (3 * nb * nb + nb          # tile + SVD U, V^T, s
                + 2 * nb * kmax           # truncated padded factors
                ) * itemsize
    own = -(-max(T - 1, 1) // n_shards)   # step group: cb x old per-column L
    n_pairs = T * (T - 1) // 2
    pps = max(-(-n_pairs // n_shards), 1)  # owned tiles per device, full sweep
    candidate = T * own                    # per-column sweep's GEN tiles
    return dict(tiles_per_step=cb * T, tiles_per_step_sharded=cb * own,
                per_tile_bytes=per_tile,
                gen_tiles_owned=pps, gen_tiles_candidate=candidate,
                gen_shrink=candidate / max(pps, 1),
                replicated_bytes=m * cb * nb * itemsize + cb * T * per_tile,
                sharded_bytes=cb * own * per_tile,
                shrink=(m * cb * nb * itemsize + cb * T * per_tile) /
                       max(cb * own * per_tile, 1))


def serve_predictions_per_sec(flops: float, byts: float, coll: float,
                              batch: int) -> float:
    """Roofline-model decode throughput of one serving predict batch:
    batch / max(compute, memory, collective time) from the trip-corrected
    per-device phase costs (the dry-run's serve_predict cell)."""
    t = max(flops / PEAK_FLOPS, byts / HBM_BW, coll / ICI_BW)
    return batch / max(t, 1e-12)


def geostat_model_flops(shape, backend: str, tile_size: int, max_rank: int) -> float:
    """Useful flops of one MLE iteration (or a cokriging prediction batch).

    exact: (1/3) m^3 Cholesky + m^2 solve     (m = p*n)
    tlr:   generator GEN (~12 flops per Sigma entry over T column panels)
           + compression SVDs (~(8/3) nb^3 per strict-lower tile)
           + T^3/6 TLR-MM-chain tasks of 36 nb kmax^2 each (paper §5.3 model)
           + T dense POTRFs + recompression QR/SVD (2 QRs of (nb, 2k)).
           The GEN/compress terms joined the model when the dry-run cell
           became the end-to-end streaming pipeline (dist_compress_tiles).
    predict: exact Cholesky + 2 triangular solves for 1 + npred*p RHS.
    """
    m = shape.matrix_dim
    if shape.kind == "predict":
        nrhs = 1 + shape.n_pred * shape.p
        return m ** 3 / 3.0 + 2.0 * m * m * nrhs
    if backend == "exact":
        return m ** 3 / 3.0 + 2.0 * m * m
    nb, k = tile_size, max_rank
    t = m // nb
    gen = 12.0 * m * m
    svd = (t * (t - 1) / 2.0) * (8.0 / 3.0) * nb ** 3
    tlr_mm = (t ** 3 / 6.0) * 36.0 * nb * k * k
    potrf = t * nb ** 3 / 3.0
    recompress = (t ** 3 / 6.0) * 2 * (2 * nb * (2 * k) ** 2)
    return gen + svd + tlr_mm + potrf + recompress


def format_report_row(r: RooflineReport) -> str:
    return (f"{r.arch:28s} {r.shape:12s} {r.mesh:8s} "
            f"compute={r.compute_s:9.3e}s memory={r.memory_s:9.3e}s "
            f"collective={r.collective_s:9.3e}s dominant={r.dominant:10s} "
            f"useful={r.useful_flops_ratio:6.3f}")
