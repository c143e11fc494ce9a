import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (architecture x input shape) cell this lowers + compiles the real
step function (train_step / prefill / decode) against the production mesh —
16x16 single-pod and 2x16x16 multi-pod — and records:

  * compiled.memory_analysis()   (per-device bytes: proves it fits)
  * compiled.cost_analysis()     (per-device FLOPs / bytes for the roofline)
  * collective wire bytes        (parsed from the partitioned HLO)
  * the three roofline terms + dominant bottleneck

Results are persisted incrementally to artifacts/dryrun/<arch>__<shape>__<mesh>.json
so a crashed/interrupted sweep resumes where it left off.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all                 # full 40-cell x 2-mesh sweep
  python -m repro.launch.dryrun --all --mesh single   # baseline roofline table
"""
import argparse
import gc
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _cell_path(arch: str, shape: str, mesh_kind: str, tag: str = "") -> Path:
    suffix = f"__{tag}" if tag else ""
    return ARTIFACTS / f"{arch}__{shape}__{mesh_kind}{suffix}.json"


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str = "single",
    *,
    optimizer: str = "adamw",
    impl: str = "auto",
    accum_override: int = 0,
    fsdp: bool = True,
    tag: str = "",
    force: bool = False,
    reduce_dtype: str = "",
    kv_dtype: str = "",
    no_fsdp: bool = False,
) -> dict:
    from repro.configs import SHAPES, get_config
    from repro.launch.hlo_stats import model_flops, parse_collectives, roofline_terms
    from repro.launch.inputs import input_specs, plan_accum
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import Model
    from repro.optim import adamw
    from repro.serving.engine import make_decode_step, make_prefill_step
    from repro.training.steps import make_train_step

    out_path = _cell_path(arch, shape_name, mesh_kind, tag)
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "tag": tag,
        "params_b": cfg.param_count() / 1e9,
        "active_params_b": cfg.active_param_count() / 1e9,
    }
    ok, reason = cfg.supports_shape(shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        _write(out_path, rec)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        n_dev = mesh.devices.size
        model = Model(cfg, param_dtype=jnp.bfloat16, impl=impl, mesh=mesh)
        kvd = {"int8": jnp.int8, "bf16": jnp.bfloat16, "": None}[kv_dtype]
        model.kv_dtype = kvd
        rec["kv_dtype"] = kv_dtype or "bf16"
        rec["reduce_dtype"] = reduce_dtype or "f32"
        rec["fsdp"] = not no_fsdp
        kind, args = input_specs(cfg, shape, mesh, optimizer_name=optimizer,
                                 kv_dtype=kvd, fsdp=not no_fsdp)
        if kind == "train":
            from jax.sharding import NamedSharding, PartitionSpec as P

            accum = accum_override or plan_accum(cfg, shape, mesh)
            rec["accum"] = accum
            opt = adamw(1e-4, weight_decay=0.1)
            param_shardings = jax.tree.map(lambda s: s.sharding, args[0].params)
            model.param_shardings = param_shardings
            rdt = {"bf16": jnp.bfloat16, "": None}[reduce_dtype]
            fn = make_train_step(
                model, opt, accum=accum, mesh=mesh, param_shardings=param_shardings,
                reduce_dtype=rdt,
            )
            rep = NamedSharding(mesh, P())
            state_shardings = jax.tree.map(lambda s: s.sharding, args[0])
            out_shardings = (state_shardings, {"loss": rep, "grad_norm": rep})
            jitted = jax.jit(fn, donate_argnums=(0,), out_shardings=out_shardings)
        elif kind == "prefill":
            jitted = jax.jit(make_prefill_step(model))
        else:
            jitted = jax.jit(make_decode_step(model), donate_argnums=(1,))

        with mesh:
            t_l = time.time()
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t_l, 2)
            t_c = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t_c, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_hbm_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            ),
        }
        ca = compiled.cost_analysis() or {}
        rec["cost_xla_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "note": "XLA counts while (scan) bodies once; see hlo_cost for trip-count-corrected totals",
        }
        text = compiled.as_text()
        rec["hlo_chars"] = len(text)
        from repro.launch.hlo_cost import analyze

        hc = analyze(text)
        del text
        rec["cost"] = {
            "flops": hc.flops,
            "bytes_accessed_upper": hc.bytes_accessed,
            "bytes_fused": hc.bytes_fused,
        }
        rec["collectives"] = dict(
            hc.collectives, total_bytes=hc.collective_bytes,
            total_count=sum(v["count"] for v in hc.collectives.values()),
        )
        rec["whiles"] = hc.whiles[:16]
        mf = model_flops(cfg, shape, n_dev)
        # memory term uses the TPU-fusion-aware byte model; the conservative
        # upper bound is recorded alongside in rec["cost"].
        rl = roofline_terms(hc.flops, hc.bytes_fused, hc.collective_bytes, mf)
        rec["roofline"] = rl.as_dict()
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug we record
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    _write(out_path, rec)
    gc.collect()
    return rec


def _write(path: Path, rec: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=2, default=str))


def main() -> None:
    from repro.configs import ASSIGNED_ARCHS, SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--impl", default="auto", choices=["auto", "direct", "flash"])
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--accum", type=int, default=0)
    ap.add_argument("--reduce_dtype", default="", choices=["", "bf16"])
    ap.add_argument("--kv_dtype", default="", choices=["", "int8", "bf16"])
    ap.add_argument("--no_fsdp", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_cell(
                    arch, shape, mesh_kind,
                    optimizer=args.optimizer, impl=args.impl,
                    accum_override=args.accum, tag=args.tag, force=args.force,
                    reduce_dtype=args.reduce_dtype, kv_dtype=args.kv_dtype,
                    no_fsdp=args.no_fsdp,
                )
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                extra = ""
                if status == "ok":
                    peak = rec["memory"]["peak_hbm_bytes"] / 2**30
                    rl = rec["roofline"]
                    extra = (
                        f"peak={peak:.2f}GiB flops/dev={rl['flops_per_device']:.3e} "
                        f"coll={rl['collective_bytes_per_device']/2**20:.1f}MiB "
                        f"bottleneck={rl['bottleneck']}"
                    )
                elif status == "error":
                    extra = rec["error"][:160]
                print(f"[{status:7s}] {arch:20s} {shape:12s} {mesh_kind:6s} "
                      f"({rec.get('total_s','-')}s) {extra}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} error={n_err}", flush=True)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
