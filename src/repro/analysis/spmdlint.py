"""SPMD-lint layer 1: jaxpr/HLO rules over a lowerable (fn + abstract args).

Every rule here is a bug class PRs 3-5 hit by hand and fixed one at a time;
the analyzer turns them into a gate.  Given a lowerable — the repo
convention ``(fn, input ShapeDtypeStructs)`` plus mesh/shardings/donation —
it traces the closed jaxpr and (optionally) compiles the SPMD program, then
reports:

  R1  replicated decomposition batches.  GSPMD has no partitioning rule for
      batched QR/SVD/eigh/POTRF-family ops, so their whole operand batch
      materializes PER DEVICE (the 13.5 GB -> 1.31 GB/device class fixed by
      shard_map in PRs 4-5).  Detected on the compiled per-device HLO: any
      decomposition custom-call whose per-device result bytes exceed the
      threshold on a multi-device mesh.  Ops already under shard_map carry
      per-device (owned-slot) shapes, so they only trip the rule when the
      per-device slice itself is a memory cliff.
  R2  donation: (a) large inputs that are dead in the jaxpr but not donated
      — a warning when an identically-shaped output exists to alias, info
      otherwise (XLA only reuses donated buffers through input-output
      aliasing; verified empirically on the CPU backend); (b) declared
      donations that failed to alias (donate_argnums bytes vs the compiled
      memory_analysis alias bytes).
  R3  densification: any intermediate with >= dense_frac * m^2 elements in
      a lowering declared TLR (``matrix_dim=m``) — the never-densify module
      contract as an analyzer rule.
  R4  dtype churn: f32<->f64 ``convert_element_type`` (including weak-type
      promotions), tabulated per source site with an in-loop flag — the
      machine-readable worklist for ROADMAP item 2 (mixed precision).
  R5  dynamic-trip-count ``while`` loops: not reverse-differentiable (the
      MLE objective needs grads) and their carried s64 index is the PR-5
      SPMD cliff; counted loops belong in core.tlr.indexed_scan (a scan
      over an int32 arange).  s64 scalar carries escalate to error.

Findings carry source locations recovered from jaxpr eqn tracebacks and
from the ``metadata={... source_file= source_line=}`` XLA threads into the
optimized HLO text, so ``# spmdlint: ignore[R..]`` comments suppress them
at the offending line (findings.SuppressionIndex).
"""
from __future__ import annotations

import dataclasses
import re
import warnings

import numpy as np

import jax

from .findings import Finding, SuppressionIndex, count_by_severity

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LintConfig:
    # R1: per-device bytes of one decomposition batch.
    replicated_warn_bytes: int = 8 * 1024 * 1024
    replicated_error_bytes: int = 256 * 1024 * 1024
    # R2: inputs smaller than this are not worth donating.
    donation_min_bytes: int = 1024 * 1024
    # R2b: declared donation counts as failed when the aliased fraction of
    # the per-device declared bytes falls below this.
    alias_min_fraction: float = 0.5
    # R3: an intermediate is "dense" at >= this fraction of m^2 elements.
    dense_frac: float = 0.25
    # R4: conversions moving fewer bytes than this stay info-level.
    convert_warn_bytes: int = 1024 * 1024


DEFAULT_CONFIG = LintConfig()


def tlr_dense_frac(tile_size: int, max_rank: int, base: float = 0.25) -> float:
    """R3 threshold (fraction of m^2 elements) for a TLR lowering.

    Legitimate tile storage is (kmax/nb) * m^2 elements (the masked T x T
    grid; half that for the pair batch), and the recompress QR works on
    rank-2k stacks [U | dU], doubling it transiently.  The densification
    bar therefore sits at TWICE the recompress peak, 4 kmax/nb * m^2 —
    which at the production geometry (kmax/nb = 1/16) is exactly the strict
    ``base`` — and never above one full m^2, so the dense Sigma itself is
    always caught.  Dev geometries with fat tiles (kmax/nb >= 1/16) would
    otherwise flag their own U/V arrays."""
    return min(max(base, 4.0 * max_rank / tile_size), 1.0)

# HLO custom-call targets of decomposition families GSPMD cannot partition
# (LAPACK on CPU, cuSOLVER on GPU, the generic lowerings elsewhere).
_DECOMP_TARGETS = ("geqrf", "orgqr", "ormqr", "householder", "gesdd", "gesvd",
                   "potrf", "getrf", "syevd", "syevj", "sytrd", "gesvdj",
                   "qr_decomposition", "eigh", "svd", "cholesky")

_CUSTOM_CALL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+custom-call\(",)
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_METADATA_RE = re.compile(
    r'metadata=\{[^}]*?(?:op_name="([^"]*)")?[^}]*?'
    r'source_file="([^"]+)"[^}]*?source_line=(\d+)')


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:
        return 0


def _eqn_source(eqn) -> tuple[str | None, int | None]:
    """Best-effort (file, line) of the user frame that traced this eqn."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            line = getattr(frame, "start_line", None) or \
                getattr(frame, "line_num", None)
            return frame.file_name, line
    except Exception:
        pass
    return None, None


def _subjaxprs(eqn):
    """All jaxprs nested in an eqn's params (scan/while/cond/pjit/shard_map/
    custom_*), normalized to open Jaxprs."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner          # ClosedJaxpr -> Jaxpr
            elif hasattr(v, "eqns"):
                yield v              # already an open Jaxpr


def _walk_eqns(jaxpr, loop_depth: int = 0):
    """Yield (eqn, loop_depth) over the whole nested jaxpr tree."""
    for eqn in jaxpr.eqns:
        yield eqn, loop_depth
        name = eqn.primitive.name
        child_depth = loop_depth + (1 if name in ("scan", "while") else 0)
        for sub in _subjaxprs(eqn):
            yield from _walk_eqns(sub, child_depth)


# ---------------------------------------------------------------------------
# Jaxpr rules: R2a, R3, R4, R5
# ---------------------------------------------------------------------------


def _donated_invars(specs, donate_argnums) -> set[int]:
    """Flat invar indices covered by donate_argnums over the given arg
    specs (each arg may be a pytree; invars are its flattened leaves)."""
    donated: set[int] = set()
    offset = 0
    for argnum, spec in enumerate(specs):
        leaves = jax.tree_util.tree_leaves(spec)
        if argnum in donate_argnums:
            donated.update(range(offset, offset + len(leaves)))
        offset += len(leaves)
    return donated


def lint_jaxpr(closed_jaxpr, *, specs=(), donate_argnums=(),
               matrix_dim: int | None = None,
               config: LintConfig = DEFAULT_CONFIG) -> list[Finding]:
    findings: list[Finding] = []
    jaxpr = closed_jaxpr.jaxpr

    # ---- R2a: large dead-but-undonated inputs -----------------------------
    donated = _donated_invars(specs, donate_argnums) if specs else set()
    outvars = {v for v in jaxpr.outvars if not hasattr(v, "val")}  # skip Literals
    out_shapes = {(tuple(v.aval.shape), str(v.aval.dtype)) for v in outvars}
    for i, var in enumerate(jaxpr.invars):
        nbytes = _aval_bytes(var.aval)
        if i in donated or nbytes < config.donation_min_bytes:
            continue
        if var in outvars:
            continue                  # passed through: donation cannot help
        key = (tuple(var.aval.shape), str(var.aval.dtype))
        aliasable = key in out_shapes
        sev = "warning" if aliasable else "info"
        how = ("an identically-shaped output exists to alias it"
               if aliasable else
               "no identically-shaped output exists, so donation would not "
               "alias — restructure (e.g. return the factor) before donating")
        findings.append(Finding(
            rule="R2", severity=sev, bytes=nbytes,
            op=f"invar[{i}]{key[0]}",
            message=f"input {i} ({key[1]}{list(key[0])}, {nbytes/1e6:.6g} MB)"
                    f" is dead after the computation but not donated; {how}"))

    # ---- walk eqns for R3/R4/R5 -------------------------------------------
    m2 = float(matrix_dim) ** 2 if matrix_dim else None
    conv_sites: dict[tuple, dict] = {}
    seen: set[tuple] = set()         # dedup pjit-wrapper/body double hits
    for eqn, depth in _walk_eqns(jaxpr):
        name = eqn.primitive.name

        wrapper = name in ("pjit", "custom_jvp_call", "custom_vjp_call",
                           "custom_vjp_call_jaxpr", "remat2", "checkpoint",
                           "closed_call")
        if m2 is not None and not wrapper:
            for out in eqn.outvars:
                aval = getattr(out, "aval", None)
                if aval is None or len(getattr(aval, "shape", ())) < 2:
                    continue
                elems = float(np.prod(aval.shape, dtype=np.float64))
                if elems >= config.dense_frac * m2:
                    src_f, src_l = _eqn_source(eqn)
                    key = ("R3", src_f, src_l, name, tuple(aval.shape))
                    if key in seen:
                        continue
                    seen.add(key)
                    findings.append(Finding(
                        rule="R3", severity="error", op=name,
                        source_file=src_f, source_line=src_l,
                        bytes=_aval_bytes(aval),
                        message=f"{name} materializes a "
                                f"{str(aval.dtype)}{list(aval.shape)} "
                                f"intermediate = {elems/m2:.2f} m^2 elements "
                                f"in a TLR lowering (m={matrix_dim}) — the "
                                f"dense Sigma must never be formed"))

        if name == "convert_element_type":
            old = eqn.invars[0].aval
            new_dtype = np.dtype(eqn.params.get("new_dtype"))
            old_dtype = np.dtype(old.dtype)
            f3264 = {np.dtype(np.float32), np.dtype(np.float64)}
            if {old_dtype, new_dtype} == f3264:
                src = _eqn_source(eqn)
                key = (src, str(old_dtype), str(new_dtype))
                site = conv_sites.setdefault(
                    key, dict(count=0, bytes=0, in_loop=False,
                              weak=bool(getattr(old, "weak_type", False))))
                site["count"] += 1
                site["bytes"] += _aval_bytes(old)
                site["in_loop"] = site["in_loop"] or depth > 0

        if name == "while":
            cond_n = eqn.params.get("cond_nconsts", 0)
            body_n = eqn.params.get("body_nconsts", 0)
            carry = eqn.invars[cond_n + body_n:]
            s64 = [v for v in carry
                   if getattr(v.aval, "shape", None) == () and
                   np.issubdtype(v.aval.dtype, np.integer) and
                   np.dtype(v.aval.dtype).itemsize == 8]
            src_f, src_l = _eqn_source(eqn)
            key = ("R5", src_f, src_l, bool(s64))
            if key in seen:
                continue
            seen.add(key)
            if s64:
                findings.append(Finding(
                    rule="R5", severity="error", op="while",
                    source_file=src_f, source_line=src_l,
                    message=f"while loop carries {len(s64)} s64 scalar(s) "
                            f"(traced or 64-bit trip bound) — the SPMD "
                            f"partitioner/reverse-diff cliff; use "
                            f"core.tlr.indexed_scan over an int32 arange"))
            else:
                findings.append(Finding(
                    rule="R5", severity="warning", op="while",
                    source_file=src_f, source_line=src_l,
                    message="dynamic-trip-count while loop: not reverse-"
                            "differentiable and opaque to trip-count cost "
                            "correction — counted loops belong in "
                            "core.tlr.indexed_scan"))

    # ---- R4 table -> findings ---------------------------------------------
    for ((src, old, new), site) in sorted(conv_sites.items(),
                                          key=lambda kv: -kv[1]["bytes"]):
        sev = ("warning" if site["in_loop"] and
               site["bytes"] >= config.convert_warn_bytes else "info")
        weak = " (weak-type promotion)" if site["weak"] else ""
        loop = " inside a scan/while body" if site["in_loop"] else ""
        findings.append(Finding(
            rule="R4", severity=sev, op=f"convert {old}->{new}",
            source_file=src[0], source_line=src[1], bytes=site["bytes"],
            message=f"{site['count']} {old}->{new} conversion(s){weak}"
                    f"{loop}, {site['bytes']/1e6:.6g} MB moved — mixed-"
                    f"precision worklist (ROADMAP item 2)"))
    return findings


def dtype_conversion_table(findings) -> list[dict]:
    """The R4 findings as machine-readable rows (ROADMAP item 2 worklist)."""
    rows = []
    for f in findings:
        if f.rule != "R4":
            continue
        rows.append(dict(source_file=f.source_file, source_line=f.source_line,
                         conversion=f.op, bytes=f.bytes,
                         in_loop="inside a scan/while" in f.message,
                         suppressed=f.suppressed))
    return rows


# ---------------------------------------------------------------------------
# Compiled-HLO rules: R1, R2b
# ---------------------------------------------------------------------------


def lint_hlo_text(hlo_text: str, *, n_devices: int,
                  config: LintConfig = DEFAULT_CONFIG) -> list[Finding]:
    """R1 over the optimized per-device HLO text."""
    from ..launch.roofline import bytes_of_type
    findings: list[Finding] = []
    if n_devices <= 1:
        return findings
    seen: set[tuple] = set()
    for line in hlo_text.splitlines():
        if "custom-call" not in line:
            continue
        tm = _TARGET_RE.search(line)
        if tm is None:
            continue
        target = tm.group(1).lower()
        if not any(t in target for t in _DECOMP_TARGETS):
            continue
        cm = _CUSTOM_CALL_RE.match(line)
        rbytes = bytes_of_type(cm.group(1)) if cm else 0
        if rbytes < config.replicated_warn_bytes:
            continue
        mm = _METADATA_RE.search(line)
        op_name, src_f, src_l = (mm.groups() if mm else (None, None, None))
        # Ops traced inside shard_map bodies already run on per-device
        # (owned-slot) operands — manual partitioning IS the R1 fix, so
        # their size only warns (a per-device slice that is itself a memory
        # cliff), never errors.
        sharded = bool(op_name) and "shmap_body" in op_name
        if sharded:
            sev = "warning"
        else:
            sev = ("error" if rbytes >= config.replicated_error_bytes
                   else "warning")
        key = (tm.group(1), src_f, src_l, rbytes)
        if key in seen:
            continue
        seen.add(key)
        how = (f"this runs under shard_map on per-device operands, but one "
               f"device's slice alone is {rbytes/1e6:.6g} MB — shrink the "
               f"owned batch (smaller tiles or more devices)"
               if sharded else
               f"GSPMD has no partitioning rule for batched QR/SVD/POTRF, "
               f"so unsharded batches replicate; run it under shard_map "
               f"over the batch axis (distribution.pair_qr / "
               f"distribution.compress_svd)")
        findings.append(Finding(
            rule="R1", severity=sev, op=tm.group(1), bytes=rbytes,
            source_file=src_f,
            source_line=int(src_l) if src_l else None,
            message=f"decomposition custom-call {tm.group(1)!r}"
                    f"{' (' + op_name + ')' if op_name else ''} holds "
                    f"{rbytes/1e6:.6g} MB PER DEVICE on a {n_devices}-device "
                    f"mesh — {how}"))
    return findings


def lint_compiled(compiled, *, n_devices: int, declared_donation_bytes: int = 0,
                  config: LintConfig = DEFAULT_CONFIG) -> list[Finding]:
    """R1 on the HLO text + R2b on memory_analysis alias accounting."""
    findings = lint_hlo_text(compiled.as_text(), n_devices=n_devices,
                             config=config)
    if declared_donation_bytes > 0:
        ms = compiled.memory_analysis()
        alias = int(getattr(ms, "alias_size_in_bytes", 0))
        per_device = declared_donation_bytes / max(n_devices, 1)
        if alias < config.alias_min_fraction * per_device:
            sev = "error" if alias == 0 else "warning"
            findings.append(Finding(
                rule="R2", severity=sev, op="donate_argnums",
                bytes=int(per_device - alias),
                message=f"declared donations cover "
                        f"{per_device/1e6:.6g} MB/device but only "
                        f"{alias/1e6:.6g} MB aliased — the donated inputs "
                        f"have no matching outputs (XLA frees nothing); "
                        f"drop the donation or return the updated buffers"))
    return findings


# ---------------------------------------------------------------------------
# Entry point: lint a lowerable
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LintReport:
    findings: list[Finding]
    summary: dict

    def errors(self):
        return [f for f in self.findings
                if f.severity == "error" and not f.suppressed]

    def to_dict(self):
        return dict(findings=[f.to_dict() for f in self.findings],
                    summary=dict(self.summary))


def summarize(findings) -> dict:
    counts = count_by_severity(findings)
    live = [f for f in findings if not f.suppressed]
    return dict(
        errors=counts["error"], warnings=counts["warning"],
        infos=counts["info"],
        suppressed=sum(1 for f in findings if f.suppressed),
        replicated_temp_bytes=sum(f.bytes for f in live if f.rule == "R1"),
        undonated_dead_bytes=sum(f.bytes for f in live
                                 if f.rule == "R2" and
                                 f.severity != "info" and
                                 f.op != "donate_argnums"),
    )


def lint_lowerable(fn, specs, *, mesh=None, in_shardings=None,
                   donate_argnums=(), matrix_dim: int | None = None,
                   compiled=None, compile: bool = True,
                   config: LintConfig = DEFAULT_CONFIG,
                   policy=None,
                   suppressions: SuppressionIndex | None = None
                   ) -> LintReport:
    """Run every rule over one lowerable; returns findings + gate metrics.

    ``compiled`` reuses an already-compiled executable (the dry-run phase
    cells); otherwise the lowerable is jitted with the given shardings and
    donations and compiled here.  ``matrix_dim`` arms the R3 densification
    rule (TLR lowerings only — the exact backend is dense by contract).
    ``policy`` (a PrecisionPolicy or its name) arms the precision-flow
    rules P1-P5 (precisionlint) over the same jaxpr.
    """
    closed = jax.make_jaxpr(fn)(*specs)
    findings = lint_jaxpr(closed, specs=specs, donate_argnums=donate_argnums,
                          matrix_dim=matrix_dim, config=config)
    if policy is not None:
        from .precisionlint import lint_precision
        findings += lint_precision(closed, policy=policy, config=config)
    n_devices = int(mesh.devices.size) if mesh is not None else 1
    declared = sum(
        _aval_bytes(leaf)
        for argnum in donate_argnums
        for leaf in jax.tree_util.tree_leaves(specs[argnum]))
    if compiled is None and compile:
        with warnings.catch_warnings():
            # An unusable donation raises a UserWarning at compile time; the
            # same defect surfaces as the R2b finding below.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            kwargs = {}
            if in_shardings is not None:
                kwargs["in_shardings"] = in_shardings
            compiled = jax.jit(fn, donate_argnums=donate_argnums,
                               **kwargs).lower(*specs).compile()
    if compiled is not None:
        findings += lint_compiled(compiled, n_devices=n_devices,
                                  declared_donation_bytes=declared,
                                  config=config)
    (suppressions or SuppressionIndex()).apply(findings)
    return LintReport(findings=findings, summary=summarize(findings))
