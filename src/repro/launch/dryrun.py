import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/roofline artifacts.

No arrays are allocated: inputs are ShapeDtypeStructs, states come from
jax.eval_shape.  This is the proof that the distribution config is coherent —
sharding mismatches, compile-time OOM and unsupported collectives all fail
here.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
Results accumulate in dryrun_results/<arch>_<shape>_<mesh>.json.
"""
import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402

from repro.configs import SHAPES, cell_applicable, get_config, input_specs, list_configs  # noqa: E402
from repro.dist.sharding import batch_shardings, cache_shardings, state_shardings  # noqa: E402
from repro.launch.mesh import make_production_mesh, peaks  # noqa: E402
from repro.roofline.hlo_costs import module_costs  # noqa: E402
from repro.roofline.report import make_row  # noqa: E402
from repro.serve import build_prefill_step, build_serve_step, cache_specs  # noqa: E402
from repro.serve.engine import step_out_shardings  # noqa: E402
from repro.train import build_train_step, train_state_specs  # noqa: E402

RESULTS_DIR = os.environ.get("DRYRUN_DIR", "dryrun_results")
# the chip the roofline rows model (the fake host devices have no kind)
MODELED_KIND = "TPU v5 lite"


def _mem_stats(compiled):
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": float(ma.argument_size_in_bytes),
            "output_bytes": float(ma.output_size_in_bytes),
            "temp_bytes": float(ma.temp_size_in_bytes),
            "alias_bytes": float(ma.alias_size_in_bytes),
        }
    except Exception:
        return None


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    """Build step fn + specs + shardings and lower.  Returns lowered."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    if shape.kind == "train":
        state_specs = train_state_specs(cfg)
        st_sh = state_shardings(cfg, mesh, state_specs)
        b_specs = input_specs(cfg, shape)
        b_sh = batch_shardings(cfg, mesh, b_specs, "train")
        step = build_train_step(cfg, mesh)
        lowered = jax.jit(step, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None),
                          donate_argnums=(0,)).lower(state_specs, b_specs)
        return lowered, cfg, shape, mesh

    # serving cells use bf16 parameters
    scfg = cfg.replace(param_dtype="bfloat16")
    p_specs = jax.eval_shape(
        lambda: __import__("repro.models", fromlist=["init_params"])
        .init_params(scfg, jax.random.PRNGKey(0)))
    p_sh = state_shardings(scfg, mesh, p_specs)

    if shape.kind == "prefill":
        b_specs = input_specs(scfg, shape)
        b_sh = batch_shardings(scfg, mesh, b_specs, "serve")
        step = build_prefill_step(scfg, mesh, cache_len=shape.seq_len)
        c_specs = cache_specs(scfg, shape.global_batch, shape.seq_len)
        c_sh = cache_shardings(scfg, mesh, c_specs)
        out_sh, _ = step_out_shardings(scfg, c_sh)
        lowered = jax.jit(step, in_shardings=(p_sh, b_sh),
                          out_shardings=out_sh).lower(p_specs, b_specs)
        return lowered, scfg, shape, mesh

    # decode
    b_specs = input_specs(scfg, shape)
    b_sh = batch_shardings(scfg, mesh, b_specs, "serve")
    c_specs = cache_specs(scfg, shape.global_batch, shape.seq_len)
    c_sh = cache_shardings(scfg, mesh, c_specs)
    step = build_serve_step(scfg, mesh)
    lowered = jax.jit(
        step, in_shardings=(p_sh, c_sh, b_sh["tokens"], b_sh["positions"]),
        out_shardings=step_out_shardings(scfg, c_sh)[1],
        donate_argnums=(1,)).lower(
            p_specs, c_specs, b_specs["tokens"], b_specs["positions"])
    return lowered, scfg, shape, mesh


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": why}
        _save(res, save)
        return res

    t0 = time.time()
    try:
        lowered, cfg2, shape2, mesh = lower_cell(arch, shape_name, multi_pod)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        txt = compiled.as_text()
        costs = module_costs(txt)
        mem = _mem_stats(compiled)
        ca = {}
        try:
            ca = {k: float(v) for k, v in (compiled.cost_analysis() or {}).items()
                  if isinstance(v, (int, float))}
        except Exception:
            pass
        chips = mesh.devices.size
        ideal = None
        if shape2.kind == "decode":
            # bytes floor: bf16 params + the whole cache, read once
            from repro.models import init_params
            p_specs = jax.eval_shape(lambda: init_params(
                cfg2, jax.random.PRNGKey(0)))
            c_specs = cache_specs(cfg2, shape2.global_batch, shape2.seq_len)
            nbytes = lambda t: sum(x.size * x.dtype.itemsize
                                   for x in jax.tree.leaves(t))
            ideal = nbytes(p_specs) + nbytes(c_specs)
        row = make_row(cfg2, shape2, mesh_name, chips, costs,
                       peaks(MODELED_KIND), mem, ideal_bytes_total=ideal)
        res = {"status": "ok", "t_lower_s": t_lower, "t_compile_s": t_compile,
               "xla_cost_analysis_flops": ca.get("flops"),
               **row.to_dict()}
    except Exception as e:  # a failing cell is a bug in the system
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    _save(res, save)
    return res


def _save(res: dict, save: bool):
    if not save:
        return
    os.makedirs(RESULTS_DIR, exist_ok=True)
    fn = f"{res['arch']}_{res['shape']}_{res['mesh']}.json"
    with open(os.path.join(RESULTS_DIR, fn), "w") as f:
        json.dump(res, f, indent=1, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list_configs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    n_ok = n_skip = n_err = 0
    for a, s, mp in cells:
        res = run_cell(a, s, mp)
        tag = res["status"]
        n_ok += tag == "ok"
        n_skip += tag == "skipped"
        n_err += tag == "error"
        if tag == "ok":
            print(f"[ok]   {a:24s} {s:12s} {res['mesh']:10s} "
                  f"comp={res['t_compute']*1e3:8.2f}ms "
                  f"mem={res['t_memory']*1e3:8.2f}ms "
                  f"coll={res['t_collective']*1e3:8.2f}ms "
                  f"bound={res['bottleneck']:10s} "
                  f"(compile {res['t_compile_s']:.0f}s)", flush=True)
        elif tag == "skipped":
            print(f"[skip] {a:24s} {s:12s} {res['mesh']:10s} {res['reason']}",
                  flush=True)
        else:
            print(f"[ERR]  {a:24s} {s:12s} {res['mesh']:10s} {res['error']}",
                  flush=True)
    print(f"\n{n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
