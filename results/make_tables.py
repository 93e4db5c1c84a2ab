"""Print the compile-only roofline table of results/dryrun.json as
markdown (run dryrun.py first).

    PYTHONPATH=src python results/make_tables.py [--mesh 16x16|2x16x16]
"""

import argparse
import json
import os

ORDER = ["graphsage-reddit", "din", "dlrm-mlperf", "dlrm-rm2", "bert4rec",
         "rmc1", "rmc2", "rmc3"]
SHAPES = {"full_graph_sm": 0, "minibatch_lg": 1, "ogb_products": 2,
          "molecule": 3, "train_batch": 0, "serve_p99": 1, "serve_bulk": 2,
          "retrieval_cand": 3}
BOUND = {"memory": "mem", "collective": "coll", "compute": "comp"}


def fmt(r):
    rf = r["roofline"]
    m = rf.get("memory") or {}
    ur = rf.get("useful_ratio")
    fits = m.get("fits_hbm_tpu", m.get("fits_hbm"))
    return (f"| {r['arch']} | {r['shape']} | {r['kind']} | "
            f"{rf['flops_per_device']:.2e} | {rf['bytes_per_device']:.2e} | "
            f"{rf['wire_bytes_per_device']:.2e} | "
            f"{rf['t_compute'] * 1e3:.1f} | {rf['t_memory'] * 1e3:.1f} | "
            f"{rf['t_collective'] * 1e3:.1f} | "
            f"**{BOUND[rf['bottleneck']]}** | "
            f"{(m.get('peak_bytes') or 0) / 1e9:.2f} | "
            f"{(m.get('tpu_peak_estimate') or m.get('peak_bytes') or 0) / 1e9:.2f} | "
            f"{'Y' if fits else 'N'} | "
            f"{('%.2f' % ur) if ur else '—'} |")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--json", default=os.path.join(
        os.path.dirname(__file__), "dryrun.json"))
    args = ap.parse_args()
    rs = json.load(open(args.json))
    rs = [r for r in rs if r["mesh"] == args.mesh]
    rs.sort(key=lambda r: (ORDER.index(r["arch"]) if r["arch"] in ORDER
                           else 99, SHAPES.get(r["shape"], 9)))
    print("| arch | shape | kind | FLOPs/dev | bytes/dev | wire/dev | "
          "t_comp (ms) | t_mem (ms) | t_coll (ms) | bound | peak GB (CPU) "
          "| peak GB (TPU est) | fits | MODEL/HLO |")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rs:
        if r["status"] == "ok":
            print(fmt(r))


if __name__ == "__main__":
    main()
