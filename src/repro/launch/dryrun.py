import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: ``lower().compile()`` every (arch x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count at first
init, and the production meshes need 512 placeholder host devices. Do not
import this module from tests (they want 1 device); run it as a script:

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both \
        --out results/dryrun.json

Per cell the script reports bytes-per-device (memory_analysis), per-device
FLOPs/bytes (cost_analysis), the collective schedule parsed from the
optimized HLO, and the three §Roofline terms. A cell failure (sharding
mismatch, OOM at compile, unsupported collective) is a bug in the system —
the run exits nonzero if any cell fails.
"""

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import get_arch, list_archs         # noqa: E402
from repro.launch import roofline as rl                     # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402


def _shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def run_cell(bundle, shape: str, mesh, multi_pod: bool) -> dict:
    step = bundle.steps[shape]
    rec = {"arch": bundle.name, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": step.kind}
    t0 = time.time()
    plan = step.make_fn(bundle, mesh, multi_pod)
    in_sh = _shardings(mesh, plan.in_specs)
    out_sh = _shardings(mesh, plan.out_specs)
    with mesh:
        jitted = jax.jit(plan.fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=plan.donate)
        lowered = jitted.lower(*plan.args)
        t1 = time.time()
        compiled = lowered.compile()
    t2 = time.time()
    n_chips = mesh.devices.size
    model_flops = (bundle.model_flops or {}).get(shape)
    roof = rl.analyze(compiled, n_chips, model_flops)
    rec.update(status="ok", seconds_lower=round(t1 - t0, 2),
               seconds_compile=round(t2 - t1, 2),
               roofline=roof.to_dict())
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="comma list or 'all' (registry names)")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--out", default=None, help="JSON output path (merged)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") == "ok"}

    n_fail = 0
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
        for name in archs:
            bundle = get_arch(name)
            shapes = (list(bundle.steps) if args.shape == "all"
                      else args.shape.split(","))
            for shape in shapes:
                if shape not in bundle.steps:
                    continue
                if (name, shape, mesh_name) in done:
                    continue
                tag = f"{name} x {shape} @ {mesh_name}"
                try:
                    rec = run_cell(bundle, shape, mesh, multi_pod)
                except Exception as e:                 # noqa: BLE001
                    rec = {"arch": name, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    n_fail += 1
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[ok]   {tag}: compile={rec['seconds_compile']}s "
                          f"flops/dev={r['flops_per_device']:.3e} "
                          f"bytes/dev={r['bytes_per_device']:.3e} "
                          f"wire/dev={r['wire_bytes_per_device']:.3e} "
                          f"bound={r['bottleneck']}"
                          + (f" peakGB="
                             f"{r['memory']['peak_bytes']/1e9:.2f}"
                             f" fits={r['memory']['fits_hbm']}"
                             if r.get("memory") else ""),
                          flush=True)
                else:
                    print(f"[FAIL] {tag}: {rec['error']}", flush=True)
                    if args.verbose:
                        print(rec["traceback"], flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"])
                           != (rec["arch"], rec["shape"], rec["mesh"])]
                results.append(rec)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".",
                                exist_ok=True)
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    print(f"\n{sum(r['status'] == 'ok' for r in results)} ok / "
          f"{n_fail} fail")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
