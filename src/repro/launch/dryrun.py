import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

__doc__ = """Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

Proves the distribution config is coherent without hardware: pjit partitions
the step function over the production mesh using ShapeDtypeStruct stand-ins
(no allocation). Records memory_analysis, cost_analysis and the collective
schedule (parsed from HLO) for the roofline report.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod] [--out DIR]
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (input_specs, install_hook,
                                   param_shardings)
from repro.models import hooks
from repro.models.model import Model
from repro.training.optimizer import adamw_init
from repro.training.train_step import make_train_step

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (per-device)
    HLO. Returns {op_name: bytes, ..., 'total': bytes, 'count': n}."""
    out = {k: 0 for k in _COLLECTIVES}
    count = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(.+?)\s*(all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start)?\(", stripped)
        if not m:
            continue
        shapes_part = m.group(1)
        op = m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(shapes_part):
            if dt not in _DTYPE_BYTES:
                continue
            size = 1
            for d in dims.split(","):
                if d:
                    size *= int(d)
            nbytes += size * _DTYPE_BYTES[dt]
        out[op] += nbytes
        count += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["count"] = count
    return out


def _cache_len(cfg, shape) -> int:
    return Model(cfg).attn_cache_len(shape.seq_len)


def build_step(cfg, shape, mesh):
    """Returns (jitted_fn, args_dict) ready to .lower(**args)."""
    model = Model(cfg)
    args, shard = input_specs(cfg, shape, mesh)
    pspecs = model.param_specs()
    psh = param_shardings(pspecs, mesh)

    if shape.kind == "train":
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.sharding import param_shardings as psh_fn
        from repro.training.optimizer import AdamWState
        step = make_train_step(model)
        opt_specs = jax.eval_shape(adamw_init, pspecs)
        # optimizer m/v shard like params PLUS across the data axes
        # (ZeRO-1): fp32 moments replicated over DP do not fit HBM
        mv_sh = psh_fn(pspecs, mesh, extra_axes=("data", "pod"))
        opt_sh = AdamWState(step=NamedSharding(mesh, P()), m=mv_sh, v=mv_sh)
        fn = jax.jit(step,
                     in_shardings=(psh, opt_sh, shard),
                     donate_argnums=(0, 1))
        lower_args = (pspecs, opt_specs, args)
        return fn, lower_args

    if shape.kind == "prefill":
        def prefill(params, batch):
            return model.prefill(params, batch["tokens"],
                                 mm_embeds=batch.get("mm_embeds"))
        fn = jax.jit(prefill, in_shardings=(psh, shard))
        return fn, (pspecs, args)

    # decode
    def decode(params, batch):
        return model.decode_step(params, batch["tokens"], batch["cache"],
                                 batch["pos"])
    fn = jax.jit(decode, in_shardings=(psh, shard),
                 donate_argnums=())
    return fn, (pspecs, args)


def _measure(cfg, shape, mesh) -> dict:
    """flops / bytes / collective bytes of one compile."""
    fn, lower_args = build_step(cfg, shape, mesh)
    lowered = fn.lower(*lower_args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": collective_bytes(compiled.as_text()),
        "compiled": compiled,
    }


def probe_corrected(cfg, shape, mesh) -> dict:
    """XLA cost_analysis counts a while-loop body once, not x trips. Probe
    with 1-unit and 2-unit *unrolled* stacks to solve
      total = nonloop + n_units * body   (per metric)
    Remainder layers (hybrid tail) are approximated as a body fraction."""
    import dataclasses
    from repro.models import transformer as tfm
    unit = len(cfg.block_pattern) if cfg.block_pattern else 1
    n_units = cfg.num_layers // unit
    rem = cfg.num_layers - n_units * unit
    cfg1 = dataclasses.replace(cfg, num_layers=unit)
    cfg2 = dataclasses.replace(cfg, num_layers=2 * unit)
    tfm.set_unroll(True)
    try:
        m1 = _measure(cfg1, shape, mesh)
        m2 = _measure(cfg2, shape, mesh)
    finally:
        tfm.set_unroll(False)

    m1.pop("compiled", None)
    m2.pop("compiled", None)

    def corr(key):
        body = m2[key] - m1[key]
        nonloop = m1[key] - body
        return max(nonloop, 0.0) + (n_units + rem / unit) * max(body, 0.0)

    coll_body = {k: m2["coll"][k] - m1["coll"][k]
                 for k in m1["coll"] if k != "count"}
    coll_nonloop = {k: m1["coll"][k] - coll_body[k] for k in coll_body}
    coll = {k: max(coll_nonloop[k], 0) + (n_units + rem / unit) * max(coll_body[k], 0)
            for k in coll_body}
    return {"flops": corr("flops"), "bytes": corr("bytes"),
            "collectives": coll}


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": mesh.size, "ok": False}
    t0 = time.time()
    try:
        install_hook(mesh)
        with mesh:
            fn, lower_args = build_step(cfg, shape, mesh)
            lowered = fn.lower(*lower_args)
            compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        ca = compiled.cost_analysis() or {}
        rec["flops"] = float(ca.get("flops", 0.0))
        rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                rec["memory"] = {
                    "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                    "output_bytes": getattr(ma, "output_size_in_bytes", None),
                    "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                    "generated_code_bytes":
                        getattr(ma, "generated_code_size_in_bytes", None),
                }
        except Exception:
            rec["memory"] = None
        rec["collectives"] = collective_bytes(compiled.as_text())
        del compiled, lowered, fn
        # roofline metrics from unrolled probes (scan bodies counted once
        # by cost_analysis — see probe_corrected)
        corr = probe_corrected(cfg, shape, mesh)
        rec["corrected"] = corr
        peak_flops = 197e12        # bf16 / chip (TPU v5e)
        hbm_bw = 819e9             # B/s / chip
        ici_bw = 50e9              # B/s / link
        rec["roofline"] = {
            "compute_s": corr["flops"] / peak_flops,
            "memory_s": corr["bytes"] / hbm_bw,
            "collective_s": corr["collectives"]["total"] / ici_bw,
        }
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["roofline"]["dominant"] = dom
        # MODEL_FLOPS (useful compute): 6*N_active*D train, 2*N_active*D fwd
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mult = 6 if shape.kind == "train" else 2
        rec["model_flops_global"] = mult * cfg.active_param_count * tokens
        rec["model_flops_per_chip"] = rec["model_flops_global"] / mesh.size
        if corr["flops"] > 0:
            rec["useful_ratio"] = rec["model_flops_per_chip"] / corr["flops"]
        rec["ok"] = True
    except Exception as e:  # a failure here is a bug in the system
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    finally:
        hooks.clear_hook()
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}_{shape_name}_{mesh_name}.json".replace("/", "_")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '?')})"
        extra = ""
        if rec["ok"]:
            extra = (f" flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e}"
                     f" coll={rec['collectives']['total']:.3e}"
                     f" t={rec['compile_s']}s")
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: {status}{extra}",
              flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    combos = []
    archs = ARCH_IDS if args.all or not args.arch else (args.arch,)
    shapes = tuple(INPUT_SHAPES) if args.all or not args.shape else (args.shape,)
    meshes = (False, True) if args.both_meshes else (args.multipod,)
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    failures = 0
    for a, s, mp in combos:
        rec = run_one(a, s, mp, args.out)
        failures += 0 if rec["ok"] else 1
    print(f"[dryrun] done: {len(combos) - failures}/{len(combos)} OK")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
