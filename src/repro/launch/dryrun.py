import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes with 512 placeholder host devices, and record the
numbers the roofline analysis needs.

  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --archs qwen2-1.5b
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi            # all
  PYTHONPATH=src python -m repro.launch.dryrun --gp                    # GP cells

Writes one JSON per cell to artifacts/dryrun/<mesh>/<arch>__<shape>.json:
memory_analysis, cost_analysis (FLOPs/bytes), and collective bytes parsed
from the optimised HLO. Failures (sharding mismatch, OOM at compile) are
bugs in the system — the run exits non-zero listing them.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, all_configs, cells, GP_CONFIGS  # noqa: E402
from repro.distributed import sharding as shlib  # noqa: E402
from repro.launch.hlo_analyzer import analyze  # noqa: E402
from repro.launch.hlo_stats import collective_bytes, cost_stats, memory_stats  # noqa: E402
from repro.launch.mesh import gp_data_axes, make_production_mesh  # noqa: E402
from repro.train import steps  # noqa: E402

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _save(out_dir: pathlib.Path, tag: str, stats: dict):
    """Write the JSON + a gzipped HLO dump for offline re-analysis."""
    import gzip
    hlo = stats.pop("_hlo_text", None)
    (out_dir / f"{tag}.json").write_text(json.dumps(stats))
    if hlo is not None:
        with gzip.open(out_dir / f"{tag}.hlo.gz", "wt") as f:
            f.write(hlo)


def _shardings_for(specs, sds, mesh):
    return shlib.tree_shardings(specs, sds, mesh)


def lower_cell(arch: str, shape_name: str, mesh, variant: str = "baseline"):
    """Lower + compile one (arch, shape) on ``mesh``; returns stats dict."""
    import dataclasses
    cfg = all_configs()[arch]
    # perf-variant knobs (see EXPERIMENTS.md §Perf)
    for v in variant.split("+"):
        if v == "flash":
            cfg = dataclasses.replace(cfg, use_flash=True)
        elif v == "a2a_int8":
            cfg = dataclasses.replace(cfg, moe_dispatch_dtype="int8")
        elif v == "cap10":
            cfg = dataclasses.replace(cfg, capacity_factor=1.0)
        elif v == "noremat":
            cfg = dataclasses.replace(cfg, remat=False)
        elif v == "remat_dots":
            cfg = dataclasses.replace(cfg, remat_policy="dots")
    shape = SHAPES[shape_name]
    t0 = time.time()
    with shlib.use_mesh(mesh):
        state_sds, specs = steps.abstract_state(cfg)
        state_sh = _shardings_for(specs, state_sds, mesh)
        batch_sds = steps.input_specs(cfg, shape)
        b_specs = steps.batch_specs(cfg, batch_sds)
        batch_sh = _shardings_for(b_specs, batch_sds, mesh)

        if shape.kind == "train":
            fn = steps.make_train_step(cfg)
            jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None))
            lowered = jitted.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            fn = steps.make_prefill_step(cfg)
            jitted = jax.jit(fn, in_shardings=(state_sh["params"], batch_sh))
            lowered = jitted.lower(state_sds["params"], batch_sds)
        else:  # decode
            fn = steps.make_serve_step(cfg)
            jitted = jax.jit(
                fn,
                in_shardings=(state_sh["params"], batch_sh["caches"],
                              batch_sh["tokens_t"], batch_sh["pos"]),
                out_shardings=(None, batch_sh["caches"]))
            lowered = jitted.lower(state_sds["params"], batch_sds["caches"],
                                   batch_sds["tokens_t"], batch_sds["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    stats = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": dict(mesh.shape), "kind": shape.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "cost": cost_stats(compiled),
        "memory": memory_stats(compiled),
        "collectives_flat": collective_bytes(hlo),
        "analyzed": analyze(hlo),       # trip-count-weighted (see hlo_analyzer)
        "n_devices": mesh.size,
        "_hlo_text": hlo,
    }
    print(f"  memory_analysis: {stats['memory']}")
    print(f"  cost_analysis(raw): {stats['cost']}")
    print(f"  analyzed(weighted): flops={stats['analyzed']['flops']:.3e} "
          f"bytes={stats['analyzed']['bytes']:.3e} "
          f"coll={stats['analyzed']['collectives'].get('total', 0):.3e}")
    return stats


def lower_gp_cell(name: str, mesh, variant: str = "mxu"):
    """Lower + compile the distributed GP bound+grad (the paper's step)."""
    from repro.core import gp_kernels as gpk
    from repro.core.distributed import DistributedGP

    gp = GP_CONFIGS[name]
    axes = gp_data_axes(mesh)
    psi2_fn = None            # "naive": paper-faithful per-point broadcast
    if variant == "mxu":      # beyond-paper MXU-matmul reformulation
        def psi2_fn(hyp, z, mu, s, w):
            return gpk.psi2_mxu(hyp, z, mu, s, w, chunk=512)

    t0 = time.time()
    eng = DistributedGP(mesh, data_axes=axes, latent=gp.latent,
                        psi2_fn=psi2_fn)
    n_pad = -(-gp.n // eng.n_shards) * eng.n_shards
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    hyp = {"log_sf2": sds((), f32), "log_ell": sds((gp.q,), f32),
           "log_beta": sds((), f32)}
    z = sds((gp.m, gp.q), f32)
    mu = sds((n_pad, gp.q), f32)
    s = sds((n_pad, gp.q), f32) if gp.latent else None
    y = sds((n_pad, gp.d), f32)
    w = sds((n_pad,), f32)
    fmask = sds((eng.n_shards,), f32)
    nf = sds((), f32)

    data_sh = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    argnums = (0, 1, 2, 3) if gp.latent else (0, 1)
    bound = eng.bound_fn(gp.d)

    def neg(hyp_, z_, mu_, s_, y_, w_, fm_, n_):
        return -bound(hyp_, z_, y_, mu_, s_, w_, fm_, n_)

    vg = jax.value_and_grad(neg, argnums=argnums)
    in_sh = (jax.tree.map(lambda _: rep, hyp), rep, data_sh,
             (data_sh if gp.latent else None), data_sh, data_sh, rep, rep)
    jitted = jax.jit(vg, in_shardings=in_sh)
    lowered = jitted.lower(hyp, z, mu, s, y, w, fmask, nf)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    stats = {
        "arch": f"gp:{name}", "shape": f"n{gp.n}_m{gp.m}", "variant": variant,
        "mesh": dict(mesh.shape), "kind": "gp_step",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "cost": cost_stats(compiled),
        "memory": memory_stats(compiled),
        "collectives_flat": collective_bytes(hlo),
        "analyzed": analyze(hlo),
        "n_devices": mesh.size,
        "_hlo_text": hlo,
    }
    print(f"  memory_analysis: {stats['memory']}")
    print(f"  cost_analysis(raw): {stats['cost']}")
    print(f"  analyzed(weighted): flops={stats['analyzed']['flops']:.3e} "
          f"bytes={stats['analyzed']['bytes']:.3e} "
          f"coll={stats['analyzed']['collectives'].get('total', 0):.3e}")
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--archs", nargs="*", default=None)
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--gp", action="store_true", help="GP cells only")
    ap.add_argument("--gp-names", nargs="*", default=None)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=str(ART))
    args = ap.parse_args()

    out_root = pathlib.Path(args.out)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    failures = []
    for multi in meshes[args.mesh]:
        mesh_name = "multi" if multi else "single"
        out_dir = out_root / mesh_name
        out_dir.mkdir(parents=True, exist_ok=True)
        mesh = make_production_mesh(multi_pod=multi)

        if args.gp:
            names = args.gp_names or list(GP_CONFIGS)
            for name in names:
                tag = f"gp_{name}__{args.variant}"
                print(f"[{mesh_name}] {tag}")
                try:
                    st = lower_gp_cell(name, mesh, args.variant)
                    _save(out_dir, tag, st)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((mesh_name, tag, repr(e)))
            continue

        cfgs = all_configs()
        archs = args.archs or sorted(cfgs)
        for arch in archs:
            for shape_name in cells(cfgs[arch]):
                if args.shapes and shape_name not in args.shapes:
                    continue
                tag = f"{arch}__{shape_name}"
                if args.variant != "baseline":
                    tag += f"__{args.variant}"
                fp = out_dir / f"{tag}.json"
                if fp.exists():
                    print(f"[{mesh_name}] {tag} (cached)")
                    continue
                print(f"[{mesh_name}] {tag}")
                try:
                    st = lower_cell(arch, shape_name, mesh, args.variant)
                    _save(out_dir, tag, st)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((mesh_name, tag, repr(e)))

    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nDRY-RUN COMPLETE")


if __name__ == "__main__":
    main()
