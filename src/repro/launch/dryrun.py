import os

from repro.jax_compat import force_host_device_count

# APPEND the device-count flag (replacing only a previous device-count
# entry): user-set XLA_FLAGS must survive a dryrun import.
force_host_device_count(512)

# Everything below runs with 512 placeholder host devices (dry-run ONLY —
# smoke tests and benches see the real single device; see the brief).
import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

# REPRO_DRYRUN_DIR overrides the artifact directory (test fixtures
# generate minimal artifacts into a tmpdir this way).
ARTIFACTS = os.environ.get("REPRO_DRYRUN_DIR") or os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

# Target-hardware constants (TPU v5e-class, per the brief)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
LINK_BW = 50e9  # B/s / link ICI


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             variant: str = "", smoke: bool = False) -> dict:
    """Lower + compile one (arch x shape x mesh) cell and write its
    roofline/HLO artifact. ``smoke=True`` swaps in the reduced config, a
    shrunken shape, and the real host mesh — a seconds-scale cell with
    the identical artifact layout, used by the test fixture that needs a
    real dryrun artifact without the full 512-device sweep."""
    import dataclasses as _dc

    from repro.core.fabric.simulator import ensure_compile_cache

    ensure_compile_cache(min_compile_secs=10.0)

    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.launch.hlo_stats import analyze
    from repro.launch.mesh import (make_host_mesh, make_production_mesh,
                                   rules_for)
    from repro.launch.steps import lower_cell

    cfg = get_config(arch)
    if variant:
        from repro.configs.opt_variants import apply_variant
        cfg = apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    if shape_name not in [s.name for s in cfg.shapes()]:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skip",
                "reason": "long_500k inapplicable: pure full-attention arch "
                          "(DESIGN.md §6)"}

    if smoke:
        cfg = cfg.reduced()
        shape = _dc.replace(shape, seq_len=min(shape.seq_len, 512),
                            global_batch=min(shape.global_batch, 8))
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    rules = rules_for(cfg, mesh)

    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, rules)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    out = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "n_devices": n_dev, "status": "ok",
           "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}
    if smoke:
        out["smoke"] = True

    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device_bytes": int(ma.argument_size_in_bytes
                                         + ma.output_size_in_bytes
                                         + ma.temp_size_in_bytes
                                         - ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        out["xla_cost_analysis"] = {
            "flops_single_visit": float(ca.get("flops", -1.0)),
            "bytes_accessed_single_visit": float(ca.get("bytes accessed", -1.0)),
        }
    except Exception as e:  # pragma: no cover
        out["xla_cost_analysis"] = {"error": str(e)}

    stats = analyze(compiled.as_text(), n_dev)
    out["hlo"] = {
        "flops_per_device": stats["flops"],
        "hbm_bytes_per_device": stats["hbm_bytes"],
        "collectives": stats["collectives"],
        "top_dots": stats["top_dots"][:8],
        "top_collectives": stats["top_collectives"][:8],
        "top_bytes": stats["top_bytes"][:12],
    }

    # --- roofline terms (seconds), single-chip denominators ---
    wire = stats["collectives"]["total"]["wire_bytes"]
    operand = stats["collectives"]["total"]["operand_bytes"]
    terms = {
        "compute_s": stats["flops"] / PEAK_FLOPS,
        "memory_s": stats["hbm_bytes"] / HBM_BW,
        "collective_s": wire / LINK_BW,
        "collective_s_simple_recipe": operand / LINK_BW,
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    out["roofline"] = terms

    # MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) — training cells only
    from repro.configs.base import SHAPES as _S
    if shape.kind == "train":
        n_active = cfg.active_param_count()
        tokens = shape.global_batch * shape.seq_len
        model_flops_global = 6.0 * n_active * tokens
        out["model_flops"] = {
            "n_params": cfg.param_count(),
            "n_active_params": n_active,
            "model_flops_global": model_flops_global,
            "model_flops_per_device": model_flops_global / n_dev,
            "useful_fraction": (model_flops_global / n_dev)
            / max(stats["flops"], 1.0),
        }
    return out


def cell_path(arch, shape, mesh_kind, variant="", smoke=False):
    base = ARTIFACTS if not variant else ARTIFACTS + "_" + variant
    os.makedirs(base, exist_ok=True)
    # smoke cells get their own filename so they can never shadow (or be
    # resumed as) a real production artifact of the same cell
    suffix = "__smoke" if smoke else ""
    return os.path.join(base, f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--mesh", default="single", choices=["single", "multi"])
    p.add_argument("--variant", default="",
                   help="optimization variant from configs/opt_variants.py; "
                        "results go to artifacts/dryrun_<variant>/")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + shrunken shape on the host "
                        "mesh: a seconds-scale cell with the same "
                        "artifact layout (test fixtures)")
    p.add_argument("--all", action="store_true",
                   help="sweep all (arch x shape x mesh) cells in "
                        "subprocesses (resumable)")
    p.add_argument("--force", action="store_true")
    p.add_argument("--timeout", type=int, default=3600)
    args = p.parse_args()
    os.makedirs(ARTIFACTS, exist_ok=True)

    if args.all:
        from repro.configs import all_arch_names
        from repro.configs.base import SHAPES
        cells = [(a, s, m) for m in ("single", "multi")
                 for a in all_arch_names() for s in SHAPES]
        done = failed = 0
        for arch, shape, mesh_kind in cells:
            path = cell_path(arch, shape, mesh_kind, args.variant)
            if os.path.exists(path) and not args.force:
                done += 1
                continue
            print(f"[dryrun] {arch} x {shape} x {mesh_kind} ...", flush=True)
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mesh_kind]
            if args.variant:
                cmd += ["--variant", args.variant]
            try:
                # children compile for 512 forced CPU host devices
                r = subprocess.run(cmd, timeout=args.timeout,
                                   env=dict(os.environ, JAX_PLATFORMS="cpu"),
                                   capture_output=True, text=True)
                if r.returncode != 0:
                    failed += 1
                    with open(path + ".err", "w") as f:
                        f.write(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
                    print(f"  FAILED (see {path}.err)", flush=True)
                else:
                    done += 1
                    print("  ok", flush=True)
            except subprocess.TimeoutExpired:
                failed += 1
                with open(path + ".err", "w") as f:
                    f.write("timeout")
                print("  TIMEOUT", flush=True)
        print(f"[dryrun] complete: {done} ok, {failed} failed", flush=True)
        return

    assert args.arch and args.shape
    try:
        out = run_cell(args.arch, args.shape, args.mesh, args.variant,
                       smoke=args.smoke)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    path = cell_path(args.arch, args.shape, args.mesh, args.variant,
                     smoke=args.smoke)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(json.dumps({k: out[k] for k in ("arch", "shape", "mesh", "status")
                      if k in out}))
    if out["status"] == "ok":
        print("memory:", out["memory"])
        print("roofline:", out["roofline"])


if __name__ == "__main__":
    main()
