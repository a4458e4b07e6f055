"""Static lint over closed jaxprs — catch chip hazards before the chip.

Every hazard class this repo has shipped so far was only discoverable
by *running* the graph; these rules find them by *walking* it. The walk
recurses through structured-control-flow sub-jaxprs (pjit, scan, while,
cond branches, shard_map, custom_vjp), so a hazard inside a decode scan
or a pipeline stage is reported with the same provenance as a top-level
one.

Rules (ids are stable; the baseline and inline suppressions key on
them):

- ``fp64-leak``      fp64/complex128 values in the graph (TPU has no
                     native fp64 — every such op runs emulated or
                     rejects at compile time) plus weak-typed f64
                     literals that silently widen neighbours.
- ``dtype-churn``    chained ``convert_element_type`` (A->B->C collapses
                     to one convert; A->B->A is pure waste) and
                     bulk narrow->wide upcasts above a byte threshold
                     (silent hot-path promotion, the flash-attention
                     mixed q/kv failure mode). INTENTIONAL int8/fp8
                     quant-dequant pairs are whitelisted when tagged —
                     issuing function name matching quant/dequant/fp8/
                     int8, or a ``# tpu-lint: quant`` marker on the
                     source line — so real narrow-dtype execution lands
                     with zero baseline growth.
- ``host-transfer``  host callbacks (``pure_callback``/``io_callback``/
                     ``debug_callback``) and ``device_put`` inside the
                     compiled region — each is a device stall.
- ``donation-miss``  large input buffers whose aval reappears in the
                     outputs undonated (optimizer state, KV slabs):
                     XLA must double-buffer them every step.
- ``collective-mesh-mismatch``  collectives whose axis names are not
                     axes of the installed ``parallel.mesh`` mesh (nor,
                     in auto mode, axes an EXPLICITLY installed
                     ``parallel.layout`` policy declares — the hybrid
                     layout's vocab-CE psum / pp state-sharding
                     collectives lint clean under a narrower installed
                     mesh; with no policy installed the rule stays
                     fully strict) — the graph can never run on the
                     fleet topology.
- ``broadcast-blowup``  non-scalar broadcasts that multiply bytes past
                     a threshold (materialized [B,H,S,S] masks etc.).
- ``collective-divergence``  cond/switch branches whose COLLECTIVE
                     SCHEDULES differ (rule body in
                     :mod:`collective_lint` — ranks disagreeing on the
                     predicate deadlock; the distributed-hang shape).
"""
from __future__ import annotations

import dataclasses
import re as _re

import numpy as np

import jax

from .findings import Finding, Report, Severity

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var  # noqa: F401


@dataclasses.dataclass
class LintConfig:
    """Thresholds for the graph rules; tests shrink them to force
    firings, the CLI uses the defaults."""

    check_fp64: bool = True
    min_donation_bytes: int = 1 << 20       # 1 MiB: opt state / KV slabs
    min_broadcast_bytes: int = 128 << 20    # materialized-mask scale
    broadcast_ratio: float = 64.0
    min_upcast_bytes: int = 32 << 20        # bulk narrow->wide promotion
    check_collective_divergence: bool = True
    mesh_axes: tuple | None = None          # None: use the global mesh
    #: auto mode only: accept axis names declared by an EXPLICITLY
    #: installed parallel.layout policy on top of the installed mesh's —
    #: a graph built for the hybrid layout (vocab-CE psum over mp, pp
    #: state-sharding collectives) lints clean even when the process
    #: currently holds a narrower mesh (e.g. the serving dp-only one).
    #: With no policy installed the rule keeps full strictness (the
    #: implicit default would whitelist every standard axis name), and
    #: explicit ``mesh_axes`` configs are honored verbatim.
    include_policy_axes: bool = True

    def resolved_mesh_axes(self):
        if self.mesh_axes is not None:
            return tuple(self.mesh_axes)
        from ..parallel import mesh as mesh_mod

        if mesh_mod.mesh_defined():
            axes = tuple(mesh_mod.get_mesh().axis_names)
            if self.include_policy_axes:
                from ..parallel import layout as layout_mod

                if layout_mod.policy_installed():
                    axes += tuple(
                        a for a in layout_mod.get_policy().axis_names()
                        if a not in axes
                    )
            return axes
        return None  # no mesh installed -> rule cannot judge, skip


_HOST_CALLBACK_PRIMS = {
    "pure_callback": Severity.ERROR,
    "io_callback": Severity.ERROR,
    "debug_callback": Severity.WARNING,  # debug_print et al.
    "device_put": Severity.WARNING,
}

# collective primitive -> params key holding the axis name(s); jax names
# drifted across versions (psum vs psum2), so match generously
_COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmax", "pmin", "ppermut", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
    "reduce_scatter_p", "pgather",
}

_WIDTH = {  # float widths for narrow->wide upcast detection
    "bfloat16": 2, "float16": 2, "float32": 4, "float64": 8,
}


def _src(eqn):
    """User frame of an eqn: 'file:line (function)' ('' when every
    frame is jax-internal)."""
    from jax._src import source_info_util

    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return ""
    return f"{fr.file_name}:{fr.start_line} ({fr.function_name})"


def _aval_str(aval):
    try:
        return f"{np.dtype(aval.dtype).name}[{','.join(map(str, aval.shape))}]"
    except Exception:
        return str(aval)


def _nbytes(aval):
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(
            aval.dtype
        ).itemsize
    except Exception:
        return 0


# quantization dtypes: a convert chain that passes through one of these
# is (when tagged) an intentional quant/dequant pair, not churn
_QUANT_DTYPES = ("int8", "uint8", "float8_e4m3fn", "float8_e5m2",
                 "float8_e4m3b11fnuz", "float8_e4m3fnuz",
                 "float8_e5m2fnuz")

# op-name pattern: converts issued from a function whose name says it
# quantizes are intentional by construction
_QUANT_FN_RE = _re.compile(r"quant|dequant|fp8|int8", _re.IGNORECASE)

_QUANT_MARKER = "# tpu-lint: quant"

_SRC_LINE_CACHE: dict = {}


def _source_line(where):
    """The source text at a ``file:line (function)`` provenance string
    (cached per file; empty on any miss)."""
    try:
        path, rest = where.split(":", 1)
        line_no = int(rest.split(" ", 1)[0])
    except (ValueError, AttributeError):
        return ""
    lines = _SRC_LINE_CACHE.get(path)
    if lines is None:
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError:
            lines = []
        _SRC_LINE_CACHE[path] = lines
    if 1 <= line_no <= len(lines):
        return lines[line_no - 1]
    return ""


def _quant_tagged(where, dtypes):
    """True when a convert chain is an INTENTIONAL int8/fp8
    quant-dequant pair: one of the chain's dtypes is a quant dtype AND
    the site is tagged — either the issuing function's name matches the
    quant pattern (quantize_kv, _fp8_dot, dequantize, ...) or the source
    line carries an explicit ``# tpu-lint: quant`` marker. Untagged
    chains through wide dtypes keep firing (real churn)."""
    if not any(np.dtype(d).name in _QUANT_DTYPES for d in dtypes):
        return False
    if "(" in (where or ""):
        # the frame carries the QUALIFIED name; only the function's own
        # name tags it, not an enclosing function's or class's
        fn_name = where.rsplit("(", 1)[1].rstrip(")").rsplit(".", 1)[-1]
        if _QUANT_FN_RE.search(fn_name):
            return True
    return _QUANT_MARKER in _source_line(where)


def _axis_names_of(eqn):
    """String axis names a collective eqn operates over (ints are
    positional vmap axes — not mesh axes, ignored)."""
    names = []
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key)
        if v is None:
            continue
        for a in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(a, str):
                names.append(a)
    return names


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for b in v:
                if isinstance(b, ClosedJaxpr):
                    yield b.jaxpr
                elif isinstance(b, Jaxpr):
                    yield b


def _walk_eqns(jaxpr):
    """Yield (eqn, producer_map) over this jaxpr and every sub-jaxpr.
    producer_map maps Var -> producing eqn *within the same jaxpr*."""
    producers = {}
    for eqn in jaxpr.eqns:
        yield eqn, producers
        for ov in eqn.outvars:
            if isinstance(ov, Var):
                producers[ov] = eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def lint_closed_jaxpr(closed, *, graph="", donated=None, config=None):
    """Run every graph rule over a ClosedJaxpr.

    ``donated``: optional sequence of bools aligned with
    ``closed.jaxpr.invars`` (True = buffer donated). Without it the
    donation rule treats every invar as undonated.
    """
    cfg = config or LintConfig()
    rep = Report()
    jaxpr = closed.jaxpr if isinstance(closed, ClosedJaxpr) else closed

    mesh_axes = cfg.resolved_mesh_axes()
    fp64_seen = set()
    churn_seen = set()
    upcast_bytes = 0
    upcast_example = ""

    # ---- constvars / literals ----------------------------------------
    if cfg.check_fp64:
        for cv in jaxpr.constvars:
            dt = getattr(cv.aval, "dtype", None)
            if dt is not None and np.dtype(dt).name in ("float64",
                                                        "complex128"):
                rep.add(Finding(
                    rule="fp64-leak", severity=Severity.ERROR,
                    message=f"fp64 constant captured by the graph: "
                            f"{_aval_str(cv.aval)}",
                    graph=graph, detail=f"const:{_aval_str(cv.aval)}",
                ))

    from .collective_lint import check_eqn_divergence

    for eqn, producers in _walk_eqns(jaxpr):
        prim = eqn.primitive.name

        # ---- collective-divergence -----------------------------------
        if cfg.check_collective_divergence and prim == "cond":
            check_eqn_divergence(eqn, graph, rep)

        # ---- fp64-leak -----------------------------------------------
        if cfg.check_fp64:
            for ov in eqn.outvars:
                dt = getattr(getattr(ov, "aval", None), "dtype", None)
                if dt is None:
                    continue
                name = np.dtype(dt).name
                if name in ("float64", "complex128"):
                    key = (prim, name, _src(eqn))
                    if key in fp64_seen:
                        continue
                    fp64_seen.add(key)
                    weak = bool(getattr(ov.aval, "weak_type", False))
                    rep.add(Finding(
                        rule="fp64-leak", severity=Severity.ERROR,
                        message=(
                            f"`{prim}` produces {name}"
                            + (" (weak-typed literal promotion)" if weak
                               else "")
                            + " — TPU has no native fp64"
                        ),
                        graph=graph, where=_src(eqn),
                        detail=f"{prim}:{name}",
                    ))

        # ---- dtype-churn ---------------------------------------------
        if prim == "convert_element_type":
            iv = eqn.invars[0]
            src_dt = np.dtype(iv.aval.dtype)
            dst_dt = np.dtype(eqn.params.get("new_dtype", src_dt))
            producer = producers.get(iv) if isinstance(iv, Var) else None
            if producer is not None and \
                    producer.primitive.name == "convert_element_type":
                first_dt = np.dtype(producer.invars[0].aval.dtype)
                path = (f"{first_dt.name}->{src_dt.name}->{dst_dt.name}")
                key = (path, _src(eqn))
                if key not in churn_seen:
                    churn_seen.add(key)
                    if _quant_tagged(_src(eqn),
                                     (first_dt, src_dt, dst_dt)):
                        # tagged int8/fp8 quant-dequant pair:
                        # intentional narrow-dtype execution, not churn
                        pass
                    else:
                        roundtrip = first_dt == dst_dt
                        rep.add(Finding(
                            rule="dtype-churn",
                            severity=Severity.WARNING,
                            message=(
                                f"chained convert {path} "
                                + ("is a round trip (pure waste)"
                                   if roundtrip
                                   else "collapses to one convert")
                            ),
                            graph=graph, where=_src(eqn), detail=path,
                        ))
            # bulk narrow->wide float promotion accounting
            sw, dw = _WIDTH.get(src_dt.name), _WIDTH.get(dst_dt.name)
            if sw and dw and dw > sw:
                nb = _nbytes(eqn.outvars[0].aval)
                upcast_bytes += nb
                if not upcast_example:
                    upcast_example = (
                        f"{src_dt.name}->{dst_dt.name} "
                        f"{_aval_str(eqn.outvars[0].aval)} at {_src(eqn)}"
                    )

        # ---- host-transfer -------------------------------------------
        if prim in _HOST_CALLBACK_PRIMS:
            rep.add(Finding(
                rule="host-transfer",
                severity=_HOST_CALLBACK_PRIMS[prim],
                message=f"`{prim}` inside the compiled region stalls the "
                        f"device on the host",
                graph=graph, where=_src(eqn), detail=f"{prim}@{_src(eqn)}",
            ))

        # ---- collective-mesh-mismatch --------------------------------
        if mesh_axes is not None and any(
            prim.startswith(p) for p in _COLLECTIVE_PRIMS
        ):
            for ax in _axis_names_of(eqn):
                if ax not in mesh_axes:
                    rep.add(Finding(
                        rule="collective-mesh-mismatch",
                        severity=Severity.ERROR,
                        message=(
                            f"collective `{prim}` over axis {ax!r} but the "
                            f"installed mesh has axes {list(mesh_axes)}"
                        ),
                        graph=graph, where=_src(eqn),
                        detail=f"{prim}:{ax}",
                    ))

        # ---- broadcast-blowup ----------------------------------------
        if prim == "broadcast_in_dim":
            out = eqn.outvars[0].aval
            inp = eqn.invars[0].aval
            in_size = int(np.prod(getattr(inp, "shape", ()) or (1,),
                                  dtype=np.int64))
            out_bytes = _nbytes(out)
            if (
                in_size > 1  # scalar broadcasts fuse; skip them
                and out_bytes >= cfg.min_broadcast_bytes
                and out_bytes / max(in_size * np.dtype(inp.dtype).itemsize,
                                    1) >= cfg.broadcast_ratio
            ):
                rep.add(Finding(
                    rule="broadcast-blowup", severity=Severity.WARNING,
                    message=(
                        f"broadcast {_aval_str(inp)} -> {_aval_str(out)} "
                        f"materializes {out_bytes >> 20} MiB in HBM"
                    ),
                    graph=graph, where=_src(eqn),
                    detail=f"{_aval_str(inp)}->{_aval_str(out)}",
                ))

    if upcast_bytes >= cfg.min_upcast_bytes:
        rep.add(Finding(
            rule="dtype-churn", severity=Severity.WARNING,
            message=(
                f"{upcast_bytes >> 20} MiB of narrow->wide float upcasts "
                f"in one graph (first: {upcast_example}) — check the hot "
                f"path keeps its storage dtype"
            ),
            graph=graph, detail=f"upcast-bytes:{upcast_bytes >> 20}MiB",
        ))

    # ---- donation-miss (top-level invars only) ------------------------
    donated = list(donated) if donated is not None else [False] * len(
        jaxpr.invars
    )
    out_avals = {}
    for ov in jaxpr.outvars:
        aval = getattr(ov, "aval", None)
        if aval is not None and getattr(aval, "shape", None) is not None:
            k = (tuple(aval.shape), np.dtype(aval.dtype).name)
            out_avals[k] = out_avals.get(k, 0) + 1
    # donated inputs pair with matching output slots FIRST — only the
    # slots left over can convict an undonated input
    for i, iv in enumerate(jaxpr.invars):
        if i < len(donated) and donated[i]:
            aval = getattr(iv, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                k = (tuple(aval.shape), np.dtype(aval.dtype).name)
                if out_avals.get(k, 0) > 0:
                    out_avals[k] -= 1
    for i, iv in enumerate(jaxpr.invars):
        aval = getattr(iv, "aval", None)
        if aval is None or getattr(aval, "shape", None) is None:
            continue
        if i < len(donated) and donated[i]:
            continue
        if _nbytes(aval) < cfg.min_donation_bytes:
            continue
        k = (tuple(aval.shape), np.dtype(aval.dtype).name)
        if out_avals.get(k, 0) > 0:
            out_avals[k] -= 1  # one output slot absorbs one candidate
            rep.add(Finding(
                rule="donation-miss", severity=Severity.WARNING,
                message=(
                    f"input #{i} {_aval_str(aval)} "
                    f"({_nbytes(aval) >> 20} MiB) matches an output aval "
                    f"but is not donated — XLA double-buffers it every "
                    f"step (donate_argnums)"
                ),
                graph=graph, detail=f"arg{i}:{_aval_str(aval)}",
            ))
    return rep


def _donated_flags(args, donate_argnums, static_argnums):
    """Per-leaf donated flags aligned with make_jaxpr's flattened
    invars (static args contribute no invars)."""
    donate = set(donate_argnums or ())
    static = set(static_argnums or ())
    flags = []
    for i, a in enumerate(args):
        if i in static:
            continue
        leaves = jax.tree_util.tree_leaves(a)
        flags.extend([i in donate] * len(leaves))
    return flags


def lint_fn(fn, *args, graph="", donate_argnums=(), static_argnums=(),
            config=None, **kwargs):
    """Trace ``fn`` with the example args and lint the resulting graph.

    ``donate_argnums`` describes the donation the *production* call site
    uses (the serving engine donates on accelerators only — pass what
    the chip path passes, or the donation rule reports its CPU-gated
    misses)."""
    closed = jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(
        *args, **kwargs
    )
    kw_leaves = sum(
        len(jax.tree_util.tree_leaves(v)) for v in kwargs.values()
    )
    donated = _donated_flags(args, donate_argnums, static_argnums)
    donated += [False] * kw_leaves
    return lint_closed_jaxpr(
        closed, graph=graph or getattr(fn, "__name__", "fn"),
        donated=donated, config=config,
    )


def lint_jitted(jitted, *args, graph="", config=None, **kwargs):
    """Lint an existing ``jax.jit``-wrapped callable, reading its real
    donation flags from the lowering (``lower().args_info``)."""
    donated = None
    try:
        info = jitted.lower(*args, **kwargs).args_info
        donated = [
            bool(getattr(leaf, "donated", False))
            for leaf in jax.tree_util.tree_leaves(info)
        ]
    except Exception:
        pass
    closed = jax.make_jaxpr(jitted)(*args, **kwargs)
    return lint_closed_jaxpr(
        closed, graph=graph or getattr(jitted, "__name__", "jitted"),
        donated=donated, config=config,
    )
