"""Roofline analysis from the dry-run's compiled artifacts.

    PYTHONPATH=src python -m benchmarks.roofline results/dryrun.json

Per (arch x shape x mesh) cell, derive the three roofline terms (seconds):

  compute    = HLO_FLOPs_per_device / 197e12          (TPU v5e bf16 peak)
  memory     = HLO_bytes_per_device / 819e9           (HBM bandwidth)
  collective = collective_result_bytes_per_device / 50e9   (per-link ICI)

Convention: collective bytes are the *result shapes* of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute ops in the
partitioned HLO — a per-device proxy for link traffic that is consistent
across baselines (ring factors ~2(N-1)/N are absorbed into the constant).

MODEL_FLOPS uses 6*N*D (train) / 2*N*D (prefill/decode) with N = active
params (MoE counts shared + top-k routed only).  The "roofline fraction" is
useful-compute-time / bottleneck-term — the score we hillclimb in §Perf.

**Geo mode** (``--geo [--smoke]``): instead of reading a dry-run file,
measure the geo kernels live — achieved vs peak bandwidth/FLOPs per
kernel from XLA cost analysis over the bench census (DESIGN.md §13).
Each run appends a ``kind: "roofline_geo"`` row to
``results/BENCH_geo.json`` so the bandwidth trajectory accumulates next
to the points/sec history.  It needs a chip listed in ``DEVICE_PEAKS``
and fails anywhere else:

    PYTHONPATH=src python -m benchmarks.roofline --geo --smoke
"""
import argparse
import json
import sys

PEAK_FLOPS = 197e12       # bf16 / chip
HBM_BW = 819e9            # B/s / chip
ICI_BW = 50e9             # B/s / link

# Published per-chip peaks (FLOP/s bf16, HBM B/s), keyed by
# ``jax.devices()[0].device_kind``.  Source: Google Cloud documentation,
# "TPU v5e".  A device that is not listed has no peaks: an error, never
# a default.
DEVICE_PEAKS = {
    "TPU v5 lite": (PEAK_FLOPS, HBM_BW),
}

sys.path.insert(0, "src")


def device_peaks(device_kind: str) -> tuple:
    """(peak FLOP/s, peak B/s) of one chip of ``device_kind``."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); roofline shares need a "
            f"chip listed in benchmarks/roofline.py") from None


def active_params(arch: str, total: int) -> int:
    """Active params per token: subtract un-routed expert weights."""
    from repro.configs import get_config
    cfg = get_config(arch)
    if not cfg.n_experts:
        return total
    expert_p = 3 * cfg.d_model * cfg.d_ff_expert
    n_moe_layers = cfg.n_layers - cfg.first_dense_layers
    routed_total = cfg.n_experts * expert_p * n_moe_layers
    routed_active = cfg.top_k * expert_p * n_moe_layers
    return total - routed_total + routed_active


def analyze(rec: dict) -> dict:
    arch, shape, kind = rec["arch"], rec["shape"], rec["kind"]
    n_dev = rec["n_devices"]
    comp = rec["flops_per_device"] / PEAK_FLOPS
    mem = rec["bytes_accessed_per_device"] / HBM_BW
    coll_bytes = sum(rec["collective_bytes_per_device"].values())
    coll = coll_bytes / ICI_BW
    terms = {"compute": comp, "memory": mem, "collective": coll}
    dominant = max(terms, key=terms.get)

    n_act = active_params(arch, rec["params"])
    b, s = rec["shape_batch_seq"] if "shape_batch_seq" in rec else (None,
                                                                   None)
    from repro.configs.base import ALL_SHAPES
    sh = {x.name: x for x in ALL_SHAPES}[shape]
    if kind == "train":
        tokens = sh.global_batch * sh.seq_len
        model_flops = 6 * n_act * tokens
    elif kind == "prefill":
        tokens = sh.global_batch * sh.seq_len
        model_flops = 2 * n_act * tokens
    else:
        tokens = sh.global_batch          # one new token per sequence
        model_flops = 2 * n_act * tokens
    mf_dev = model_flops / n_dev
    useful = mf_dev / PEAK_FLOPS
    bottleneck_t = max(terms.values())
    return {
        "arch": arch, "shape": shape, "mesh": rec["mesh_name"],
        "kind": kind,
        "compute_s": comp, "memory_s": mem, "collective_s": coll,
        "dominant": dominant,
        "model_flops_per_dev": mf_dev,
        "hlo_flops_per_dev": rec["flops_per_device"],
        "useful_flop_ratio": (mf_dev / rec["flops_per_device"]
                              if rec["flops_per_device"] else 0.0),
        "roofline_fraction": useful / bottleneck_t if bottleneck_t else 0.0,
        "temp_gib": rec["memory"]["temp_size"] / 2**30,
        "args_gib": rec["memory"]["argument_size"] / 2**30,
    }


def improvement_hint(a: dict) -> str:
    if a["dominant"] == "compute":
        if a["useful_flop_ratio"] < 0.5:
            return ("cut non-model FLOPs (remat recompute, causal-masked "
                    "waste, replicated head compute)")
        return "compute-bound near useful flops; raise MXU util via tiling"
    if a["dominant"] == "memory":
        return ("cut HBM traffic: fuse/bf16 intermediates, larger attention "
                "chunks, avoid logit materialization")
    return "reduce collective volume: reshard weights, overlap, or cast " \
           "all-gathers to bf16"


def compiled_cost(compiled) -> dict:
    """(flops, bytes accessed) from a jax compiled artifact's cost
    analysis — tolerant of the dict-vs-singleton-list return shape that
    varies across jax versions, and of missing keys (TPU interpret)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def geo_roofline(name: str, fn, args_: tuple, n_points: int,
                 repeats: int = 3) -> dict:
    """One achieved-vs-peak row for a geo kernel: compile ``fn`` once,
    take FLOPs + bytes from the compiled cost analysis, wall time from a
    median of ``repeats`` runs, and divide by the device-kind peaks."""
    import jax

    from benchmarks import common

    device_kind = jax.devices()[0].device_kind
    peak_flops, peak_bw = device_peaks(device_kind)
    f = jax.jit(fn)
    cost = compiled_cost(f.lower(*args_).compile())
    dt, _ = common.timeit(f, *args_, repeats=repeats)
    achieved_bw = cost["bytes_accessed"] / dt
    achieved_flops = cost["flops"] / dt
    bw_frac = achieved_bw / peak_bw
    flop_frac = achieved_flops / peak_flops
    return {
        "kernel": name, "n_points": int(n_points),
        "device_kind": device_kind,
        "wall_ms": dt * 1e3, "pts_per_sec": n_points / dt,
        "flops": cost["flops"], "bytes_accessed": cost["bytes_accessed"],
        "bytes_per_point": cost["bytes_accessed"] / max(n_points, 1),
        "achieved_bw": achieved_bw, "achieved_flops": achieved_flops,
        "bw_fraction": bw_frac, "flop_fraction": flop_frac,
        # Distance to the nearest roof — the score the tile sweep
        # (geo_perf --autotune) hillclimbs.
        "roofline_fraction": max(bw_frac, flop_frac),
        "dominant": "memory" if bw_frac >= flop_frac else "compute",
    }


def geo_main(smoke: bool) -> None:
    """Live achieved-bandwidth rows for the geo strategies (see module
    docstring); appends one roofline_geo run to results/BENCH_geo.json."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks import common
    from repro.core.engine import EngineConfig, GeoEngine
    from repro.core.strategies import onepass_runs

    n = int(min(1 << 18, 20_000 if smoke else 1 << 18))
    census = common.get_census().census
    cov = common.get_covering(9)
    xy, _, *_ = common.sample_points(n)
    pts = jnp.asarray(xy, jnp.float32)
    specs = {
        "fast_exact": ("fast", EngineConfig(mode="exact", fused=True)),
    }
    if onepass_runs():
        specs["fast_onepass"] = ("fast_onepass", EngineConfig())
    kernels = {}
    device_kind = jax.devices()[0].device_kind
    device_peaks(device_kind)          # no chip in the table: fail first
    print(f"geo roofline: n={n} points, device={device_kind}"
          + (" [smoke]" if smoke else ""))
    for name, (strategy, cfg) in specs.items():
        eng = GeoEngine.build(census, strategy, cfg, covering=cov)
        row = geo_roofline(name, lambda p, e=eng: e.assign(p).block,
                           (pts,), n, repeats=3 if smoke else 5)
        kernels[name] = row
        print(f"{name:14s}: {row['wall_ms']:7.1f}ms "
              f"({row['pts_per_sec']/1e6:5.2f}M pts/s) | "
              f"{row['achieved_bw']/1e9:6.2f} GB/s "
              f"({row['bw_fraction']*100:5.2f}% of peak) | "
              f"{row['bytes_per_point']:6.0f} B/pt | {row['dominant']}")
    run = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "kind": "roofline_geo", "smoke": bool(smoke),
           "n_points": n, "device_kind": device_kind,
           "kernels": kernels}
    n_runs = common.append_bench_run(run)
    print(f"wrote {common.BENCH_GEO_PATH} ({n_runs} runs)")


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="results/dryrun.json")
    ap.add_argument("--mesh", default=None,
                    choices=(None, "single_pod", "multi_pod"))
    ap.add_argument("--json-out", default="results/roofline.json")
    ap.add_argument("--geo", action="store_true",
                    help="live geo-kernel achieved-bandwidth rows "
                         "instead of dry-run analysis")
    ap.add_argument("--smoke", action="store_true",
                    help="with --geo: verify-sized batch")
    args = ap.parse_args()
    if args.geo:
        geo_main(args.smoke)
        return
    recs = json.load(open(args.path))
    rows = []
    for r in recs:
        if not r.get("ok"):
            continue
        if args.mesh and r["mesh_name"] != args.mesh:
            continue
        rows.append(analyze(r))
    rows.sort(key=lambda a: (a["mesh"], a["arch"], a["shape"]))

    hdr = (f"| arch | shape | mesh | compute(s) | memory(s) | coll(s) | "
           f"dominant | useful/HLO | roofline frac | temp GiB |")
    print(hdr)
    print("|" + "---|" * 10)
    for a in rows:
        print(f"| {a['arch']} | {a['shape']} | {a['mesh']} "
              f"| {a['compute_s']:.3e} | {a['memory_s']:.3e} "
              f"| {a['collective_s']:.3e} | **{a['dominant']}** "
              f"| {a['useful_flop_ratio']:.2f} "
              f"| {a['roofline_fraction']:.3f} | {a['temp_gib']:.1f} |")
    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"\nwrote {args.json_out}")
    # Hillclimb-candidate summary (single-pod train/prefill cells).
    sp = [a for a in rows if a["mesh"] == "single_pod"]
    if sp:
        worst = min(sp, key=lambda a: a["roofline_fraction"])
        coll = max(sp, key=lambda a: a["collective_s"]
                   / max(max(a["compute_s"], a["memory_s"]), 1e-12))
        print(f"\nworst roofline fraction: {worst['arch']} x "
              f"{worst['shape']} ({worst['roofline_fraction']:.3f}, "
              f"{worst['dominant']}-bound) -> {improvement_hint(worst)}")
        print(f"most collective-bound: {coll['arch']} x {coll['shape']} "
              f"(coll/max(other)="
              f"{coll['collective_s']/max(max(coll['compute_s'], coll['memory_s']), 1e-12):.2f})"
              f" -> {improvement_hint(coll)}")


if __name__ == "__main__":
    main()
