import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Deliverable (g): per (arch x shape x mesh) roofline table from the
dry-run — compute/memory/collective terms (seconds), dominant bottleneck,
MODEL_FLOPS / HLO_FLOPs ratio, and a one-line lever per cell.

Reads cached dry-run JSONs when fresh, otherwise recompiles the cell.
"""
import glob
import json
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import SHAPES, get_config, list_archs  # noqa: E402
from repro.launch import compile_cache
from repro.launch import dryrun  # noqa: E402

from common import save_json  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def lever(row) -> str:
    """One sentence: what would move the dominant term down."""
    dom = row["roofline"]["dominant"]
    pol = row["policy"]
    if dom == "compute_s":
        if row["roofline"]["useful_flops_ratio"] < 0.5:
            return ("cut non-useful FLOPs: relax remat policy "
                    f"(now {pol['remat']}) or reduce MoE capacity padding")
        return "compute-bound near useful work: scale batch or accept"
    if dom == "memory_s":
        return ("cut HBM traffic: larger microbatches amortize param reads; "
                "fuse/avoid layout copies; bf16 params"
                if row["shape"].startswith("train")
                else "cut HBM traffic: shard KV/state further, bf16 params")
    return ("cut wire bytes: fewer weight re-gathers (microbatch/remat "
            "interaction), gradient compression on the pod axis, or a "
            "sharding preset with cheaper collectives")


def run_all(mesh_kinds=("single", "multi")):
    rows = []
    for arch in list_archs():
        for shape in SHAPES:
            for mk in mesh_kinds:
                tag = f"{arch}__{shape}__{mk}"
                path = os.path.join(RESULTS_DIR, tag + ".json")
                res = None
                if os.path.exists(path):
                    with open(path) as f:
                        res = json.load(f)
                if res is None or res.get("status") not in ("ok", "skipped"):
                    res = dryrun.run_cell(arch, shape, mk == "multi")
                    os.makedirs(RESULTS_DIR, exist_ok=True)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1, default=str)
                rows.append(res)
    return rows


def render(rows) -> str:
    hdr = ("| arch | shape | mesh | compute_s | memory_s | collective_s | "
           "dominant | MODEL/HLO | useful | peak GiB | lever |")
    sep = "|" + "---|" * 11
    lines = [hdr, sep]
    for r in rows:
        if r.get("status") == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"— | — | — | skipped | — | — | — | {r['reason'][:60]} |")
            continue
        ro = r["roofline"]
        mk = r.get("mesh_kind", "?")
        lines.append(
            f"| {r['arch']} | {r['shape']} | {mk} | "
            f"{ro['compute_s']*1e3:.1f}ms | {ro['memory_s']*1e3:.1f}ms | "
            f"{ro['collective_s']*1e3:.1f}ms | {ro['dominant'].replace('_s','')} | "
            f"{ro['model_flops_ratio']:.3f} | {ro['useful_flops_ratio']:.3f} | "
            f"{r['memory']['peak_bytes']/2**30:.1f} | {lever(r)[:80]} |")
    return "\n".join(lines)


def main():
    compile_cache.enable()
    t0 = time.time()
    rows = run_all()
    md = render(rows)
    out = os.path.join(os.path.dirname(__file__), "results",
                       "roofline_table.md")
    with open(out, "w") as f:
        f.write(md + "\n")
    ok = [r for r in rows if r.get("status") == "ok"]
    skipped = [r for r in rows if r.get("status") == "skipped"]
    worst = sorted(ok, key=lambda r: r["roofline"]["roofline_fraction"])[:3]
    print(md, flush=True)
    print(f"bench_roofline,cells_ok={len(ok)},skipped={len(skipped)},"
          f"worst_fraction={worst[0]['roofline']['roofline_fraction']:.3f},"
          f"wall_s={time.time()-t0:.0f}", flush=True)
    save_json("bench_roofline.json",
              {"n_ok": len(ok), "n_skipped": len(skipped),
               "wall_s": time.time() - t0})


if __name__ == "__main__":
    main()
