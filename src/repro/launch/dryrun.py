"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
and extract memory / cost / collective roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results are written incrementally to results/dryrun/<arch>__<shape>__<mesh>.json
(existing files are skipped — the matrix run is resumable).
"""
# The 512 placeholder devices MUST be configured before any jax import —
# jax locks the device count on first backend initialisation. They are
# CPU devices: the dry-run is compile-only and must never take a chip.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
_N_DEV = os.environ.get("DRYRUN_DEVICES", "512")
os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={_N_DEV} "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import INPUT_SHAPES, ARCH_IDS, get_config  # noqa: E402
from repro.launch import specs as S                            # noqa: E402
from repro.launch.mesh import (                                # noqa: E402
    HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh)
from repro.launch.steps import (                               # noqa: E402
    make_decode_step, make_prefill_step, make_train_step)
from repro.models import build                                 # noqa: E402
from repro.optim import make_optimizer                         # noqa: E402

RESULTS_DIR = os.environ.get("DRYRUN_DIR", "results/dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
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


def _split_computations(hlo_text: str):
    """-> (comp_lines: name -> [instruction lines], entry name)."""
    comp_lines = {}
    entry = None
    comp = None
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and "(" in line:
            m = re.match(r"^\s*(ENTRY\s+)?%?([\w\.\-]+)\s*\(", line)
            if m:
                comp = m.group(2)
                comp_lines[comp] = []
                if m.group(1):
                    entry = comp
                continue
        if comp is not None:
            comp_lines.setdefault(comp, []).append(line)
    return comp_lines, entry


def _comp_multipliers(comp_lines: dict, entry):
    """Per-computation execution multiplier from the call graph: while-loop
    bodies get their trip count (XLA's known_trip_count, falling back to
    the largest constant in the loop condition — lax.scan lowers to
    `counter < N`); fusion/call/cond targets inherit their caller's count.
    Returns (mult, called_set, unknown_trips)."""
    edges = []
    called = set()
    unknown_trips = 0
    for parent, lines in comp_lines.items():
        for line in lines:
            if re.search(r"\bwhile\(", line):
                mb = re.search(r"body=%?([\w\.\-]+)", line)
                mc = re.search(r"condition=%?([\w\.\-]+)", line)
                mt = re.search(r"trip_count[\"':\s=\{]*n?[\"':\s]*(\d+)", line)
                if mb:
                    if mt:
                        t = int(mt.group(1))
                    else:
                        t = 1
                        cond_lines = comp_lines.get(mc.group(1), []) if mc else []
                        consts = [int(x) for cl in cond_lines
                                  for x in re.findall(r"constant\((\d+)\)", cl)]
                        if consts:
                            t = max(consts)
                        else:
                            unknown_trips += 1
                    edges.append((parent, mb.group(1), t))
                    if mc:
                        edges.append((parent, mc.group(1), t))
            for mm in re.finditer(
                    r"(?:to_apply|calls|branch_computations|true_computation|"
                    r"false_computation|called_computations)="
                    r"[\{]?%?([\w\.\-]+)", line):
                edges.append((parent, mm.group(1), 1))
                called.add(mm.group(1))

    mult = {c: 0 for c in comp_lines}
    if entry:
        mult[entry] = 1
    else:
        mult = {c: 1 for c in comp_lines}
    changed = True
    while changed:
        changed = False
        for p, b, t in edges:
            if p in mult and b in mult and mult[p] * t > mult[b]:
                mult[b] = mult[p] * t
                changed = True
    for c in mult:
        if mult[c] == 0:
            mult[c] = 1  # unreached by our walk — count once, never drop
    # innermost-loop trip per computation: while bodies get their own trip;
    # computations called from a body inherit the caller's (fusions etc.)
    own_trip = {c: 1 for c in comp_lines}
    changed = True
    while changed:
        changed = False
        for p, b, t in edges:
            cand = t if t > 1 else own_trip.get(p, 1)
            if b in own_trip and cand > own_trip[b]:
                own_trip[b] = cand
                changed = True
    return mult, called, unknown_trips, own_trip


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Per-device result bytes of collective ops in post-SPMD HLO,
    weighted by loop trip counts."""
    comp_lines, entry = _split_computations(hlo_text)
    mult, _, unknown_trips, _ = _comp_multipliers(comp_lines, entry)
    per_op = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for comp, lines in comp_lines.items():
        m = mult[comp]
        for line in lines:
            for c in _COLLECTIVES:
                if re.search(rf"\b{c}(-start)?\(", line) and "=" in line:
                    lhs = line.split("=", 1)[0]
                    b = _shape_bytes(lhs)
                    if b == 0:
                        b = _shape_bytes(line.split("=", 1)[1])
                    per_op[c] += b * m
                    counts[c] += m
    return {"bytes_per_device": per_op, "counts": counts,
            "total_bytes_per_device": sum(per_op.values()),
            "unknown_trip_counts": unknown_trips}


_DOT_RE = re.compile(r"=\s*\S+\s+dot\(")


def hlo_costs(hlo_text: str) -> dict:
    """Trip-count-aware per-device FLOPs and HBM bytes from post-SPMD HLO.

    XLA's compiled.cost_analysis() counts while-loop bodies ONCE (verified
    empirically — flops identical for 2- vs 8-iteration scans), which makes
    it useless for scan-over-layers models; this walker multiplies by the
    loop trip counts instead.

    FLOPs: 2 * prod(result_dims) * prod(contracted_dims) per dot op, plus
    1 flop/element for non-dot ops (elementwise estimate).
    Bytes: operand + result bytes of top-level instructions (fusion
    interiors excluded — they stay in registers/VMEM).
    """
    comp_lines, entry = _split_computations(hlo_text)
    mult, called, unknown_trips, own_trip = _comp_multipliers(comp_lines, entry)

    # name -> dims table (post-opt HLO references operands by name only)
    def_re = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\w+)\[([\d,]*)\]")
    shapes = {}
    for lines in comp_lines.values():
        for line in lines:
            md = def_re.match(line)
            if md:
                dims = [int(x) for x in md.group(3).split(",") if x]
                shapes[md.group(1)] = (dims, md.group(2))

    flops = 0.0
    dot_flops = 0.0
    bytes_acc = 0.0
    dot_misses = 0
    for comp, lines in comp_lines.items():
        m = mult[comp]
        top_level = comp not in called   # fusion interiors don't touch HBM
        for line in lines:
            md = def_re.match(line)
            if not md:
                continue
            res_dims = [int(x) for x in md.group(3).split(",") if x]
            res_dt = md.group(2)
            rn = 1
            for dd in res_dims:
                rn *= dd
            res_bytes = rn * _DTYPE_BYTES.get(res_dt, 4)
            # rhs body after the result shape
            rhs = line.split("=", 1)[1]
            mop = re.match(r"\s*\S+\s+([\w\-]+)", rhs)
            opname = mop.group(1) if mop else ""
            if _DOT_RE.search(line):
                mo = re.search(r"dot\(\s*%?([\w\.\-]+)", rhs)
                mc_ = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rhs)
                cn = 1
                if mo and mo.group(1) in shapes and mc_:
                    lhs_dims = shapes[mo.group(1)][0]
                    for ci in (int(x) for x in mc_.group(1).split(",") if x):
                        if ci < len(lhs_dims):
                            cn *= lhs_dims[ci]
                else:
                    dot_misses += 1
                f = 2.0 * rn * cn
                flops += f * m
                dot_flops += f * m
            else:
                flops += rn * m  # elementwise estimate
            if not top_level:
                continue
            # --- HBM-traffic model per top-level instruction ---
            if opname in ("parameter", "constant", "tuple", "get-tuple-element",
                          "bitcast", "while", "conditional", "call",
                          "after-all", "iota", "partition-id", "replica-id"):
                continue  # views / control flow: interiors counted separately
            trip = own_trip.get(comp, 1)
            iname = md.group(1)  # instruction name encodes fused ops
            if opname in ("dynamic-slice", "slice", "gather"):
                bytes_acc += 2 * res_bytes * m        # read slice + write
                continue
            if (opname in ("dynamic-update-slice", "scatter")
                    or (opname == "fusion" and "dynamic-update-slice" in iname)):
                # in-place slice write inside a loop: the buffer is written
                # fully ONCE across the loop, not per iteration
                bytes_acc += 2 * res_bytes * m / max(trip, 1)
                continue
            sliced_read = opname == "fusion" and "dynamic-slice" in iname
            b = res_bytes                              # result write
            for op in re.findall(r"%([\w\.\-]+)", rhs.split("metadata")[0]):
                if op in shapes:
                    dims, dt = shapes[op]
                    n = 1
                    for dd in dims:
                        n *= dd
                    ob = n * _DTYPE_BYTES.get(dt, 4)
                    if trip > 1 and opname == "fusion" and ob > res_bytes \
                            and not re.search(r"dot|reduce|conv", iname):
                        # big buffer consumed by a smaller-output fusion in
                        # a loop body => sliced access; cap at one full read
                        # per loop (ob/trip) or the output size
                        ob = max(res_bytes if not sliced_read else 0,
                                 ob / trip)
                    b += ob
            bytes_acc += b * m
    return {"flops": flops, "dot_flops": dot_flops, "bytes": bytes_acc,
            "unknown_trip_counts": unknown_trips, "dot_misses": dot_misses}


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = new tokens only."""
    model = build(cfg)
    counts = model.param_count()
    n = counts["active"]
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_lowered(arch: str, shape_name: str, multi_pod: bool,
                  opt_name: str = "adamw", recipe: str = "default",
                  microbatches: int = 1):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    model = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if recipe == "fsdp":    # pure ZeRO: batch covers every mesh axis
        os.environ["REPRO_BATCH_AXES"] = "pod,data,model"
    else:
        os.environ.pop("REPRO_BATCH_AXES", None)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = S.param_shardings(mesh, params_shapes, cfg, recipe)
    repl = S.replicated(mesh)

    if shape.kind == "train":
        optimizer = make_optimizer(opt_name, 1e-4)
        opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
        # optimizer state mirrors the parameter tree's sharding
        from repro.optim.optimizers import AdamState
        if isinstance(opt_shapes, AdamState):
            o_shard = AdamState(mu=p_shard, nu=p_shard, count=repl)
        elif opt_shapes == ():
            o_shard = repl
        else:
            o_shard = p_shard
        batch_specs = S.input_specs(cfg, shape)
        b_shard = S.batch_shardings(mesh, batch_specs, shape)
        step = make_train_step(model, optimizer,
                               microbatches=microbatches)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, repl),
                         donate_argnums=(0, 1))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(params_shapes, opt_shapes, batch_specs)
    elif shape.kind == "prefill":
        batch_specs = S.input_specs(cfg, shape)
        b_shard = S.batch_shardings(mesh, batch_specs, shape)
        step = make_prefill_step(model, shape)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(params_shapes, batch_specs)
    else:  # decode
        c_specs = S.cache_specs(model, cfg, shape)
        c_shard = S.cache_shardings(mesh, c_specs, cfg, shape)
        tok_spec = S.sds((shape.global_batch, 1), jnp.int32)
        t_shard = S.batch_shardings(mesh, tok_spec, shape)
        step = make_decode_step(model)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, c_shard, t_shard),
                         out_shardings=(repl, c_shard),
                         donate_argnums=(1,))
        with jax.sharding.set_mesh(mesh):
            lowered = jitted.lower(params_shapes, c_specs, tok_spec)
    return lowered, mesh, cfg, shape


def analyze(lowered, compiled, mesh, cfg, shape) -> dict:
    n_chips = mesh.devices.size
    out = {"n_chips": int(n_chips)}
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        live = (out["memory"].get("argument_size_in_bytes", 0)
                + out["memory"].get("temp_size_in_bytes", 0))
        out["memory"]["per_device_total_gb"] = live / 1e9
        out["memory"]["fits_v5e_16gb"] = bool(live < 16e9)
    except Exception as e:  # pragma: no cover
        out["memory_error"] = repr(e)
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        # NOTE: XLA counts while bodies once — kept only for reference.
        out["cost_xla_one_body"] = {
            k: float(cost[k]) for k in ("flops", "bytes accessed")
            if k in cost}
    except Exception as e:  # pragma: no cover
        out["cost_error"] = repr(e)
    hlo = compiled.as_text()
    out["collectives"] = collective_bytes_from_hlo(hlo)
    out["cost"] = hlo_costs(hlo)
    out["hlo_bytes"] = len(hlo)

    flops_per_dev = out["cost"]["dot_flops"]   # MXU work (roofline compute)
    bytes_per_dev = out["cost"]["bytes"]
    coll_per_dev = out["collectives"]["total_bytes_per_device"]
    mf = model_flops(cfg, shape)
    out["roofline"] = {
        "hlo_flops_per_device": flops_per_dev,
        "hlo_flops_with_elementwise": out["cost"]["flops"],
        "hlo_bytes_per_device": bytes_per_dev,
        "collective_bytes_per_device": coll_per_dev,
        "t_compute_s": flops_per_dev / PEAK_FLOPS_BF16,
        "t_memory_s": bytes_per_dev / HBM_BW,
        "t_collective_s": coll_per_dev / ICI_BW,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops_per_dev
        if flops_per_dev else None,
    }
    terms = {k: out["roofline"][f"t_{k}_s"]
             for k in ("compute", "memory", "collective")}
    out["roofline"]["dominant"] = max(terms, key=terms.get)
    return out


class _FakeCompiled:
    """Re-analysis stand-in built from a cached HLO dump."""

    def __init__(self, hlo):
        self._hlo = hlo

    def as_text(self):
        return self._hlo

    def memory_analysis(self):
        raise RuntimeError("no memory analysis in reanalyze mode")

    def cost_analysis(self):
        raise RuntimeError("no xla cost analysis in reanalyze mode")


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str = RESULTS_DIR, force: bool = False,
            opt_name: str = "adamw", reanalyze: bool = False,
            recipe: str = "default") -> dict:
    import gzip
    mesh_name = "multipod" if multi_pod else "singlepod"
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch}__{shape_name}__{mesh_name}"
    if recipe != "default":
        stem += f"__{recipe}"
    path = os.path.join(out_dir, f"{stem}.json")
    hlo_path = os.path.join(out_dir, f"{stem}.hlo.gz")
    if os.path.exists(path) and not force and not reanalyze:
        with open(path) as f:
            return json.load(f)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "error"}
    t0 = time.time()
    try:
        if reanalyze and os.path.exists(hlo_path) and os.path.exists(path):
            with open(path) as f:
                old = json.load(f)
            with gzip.open(hlo_path, "rt") as f:
                hlo = f.read()
            cfg = get_config(arch)
            shape = INPUT_SHAPES[shape_name]
            mesh = make_production_mesh(multi_pod=multi_pod)
            rec.update(analyze(None, _FakeCompiled(hlo), mesh, cfg, shape))
            rec["memory"] = old.get("memory")       # keep compile-time facts
            rec["lower_s"] = old.get("lower_s")
            rec["compile_s"] = old.get("compile_s")
            rec["status"] = "ok"
            print(f"RE  {arch:24s} {shape_name:12s} {mesh_name:9s} "
                  f"dom={rec['roofline']['dominant']}", flush=True)
        else:
            lowered, mesh, cfg, shape = build_lowered(arch, shape_name,
                                                      multi_pod, opt_name,
                                                      recipe=recipe)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            with gzip.open(hlo_path, "wt") as f:
                f.write(compiled.as_text())
            rec.update(analyze(lowered, compiled, mesh, cfg, shape))
            rec["status"] = "ok"
            rec["lower_s"] = t1 - t0
            rec["compile_s"] = t2 - t1
            print(f"OK  {arch:24s} {shape_name:12s} {mesh_name:9s} "
                  f"lower {t1-t0:6.1f}s compile {t2-t1:6.1f}s "
                  f"dom={rec['roofline']['dominant']}", flush=True)
    except Exception as e:
        rec["error"] = traceback.format_exc()
        print(f"ERR {arch:24s} {shape_name:12s} {mesh_name:9s}: {e!r}",
              flush=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute analysis from cached .hlo.gz (no compile)")
    ap.add_argument("--opt", default="adamw")
    ap.add_argument("--recipe", default="default",
                    choices=["default", "tp_serve", "fsdp"])
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args(argv)

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_ok = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_one(arch, shape, mp, out_dir=args.out,
                              force=args.force, opt_name=args.opt,
                              reanalyze=args.reanalyze,
                              recipe=args.recipe)
                n_ok += rec.get("status") == "ok"
                n_err += rec.get("status") != "ok"
    print(f"done: {n_ok} ok, {n_err} errors")


if __name__ == "__main__":
    main()
