"""Readings that the limits of `correct` are set from, on the chip at the
cell's own size:

  program   the program against the float32 reference, on every seed;
  control   the reference computed with fp8 matmul operands, against the
            float32 reference, on the first `--control-seeds` seeds;
  half      the reference on half of each client's rows (the half-batch
            fault, planted in the reference put in the program's place),
            against the float32 reference, on the same seeds.

  python3 chipbench/calibrate.py --workload mtb16_vt_cls --seeds 1,2,3 \
      --out chipbench/out/calibrate.jsonl

One JSON line per seed, with every leaf's norms, and a summary line: the
largest program reading (the lower one), the smallest control and fault
readings (upper), and each upper reading over the lower. A state left unchanged reads 1 on
grad_gap and update_gap by definition and needs no run.
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _leaves(readings):
    return {k: readings[k] for k in ("grad", "delta")}


def calibrate(workload, seeds, control_seeds, *, log=lambda rec: None,
              **run_kw):
    """(rows, summary) over `seeds`; `run_kw` goes to `harness.run_cell`
    (CPU tests pass `require=False` and a reduced cell)."""
    from chipbench import compare, faults, harness
    from chipbench.reference import common
    rows = []
    for i, seed in enumerate(seeds):
        keep = {}
        r = harness.run_cell(workload, seed, 0, False, measure=False,
                             keep=keep, **run_kw)
        row = {"seed": seed,
               "program": {k: c["value"] for k, c in r["checks"].items()},
               "losses": keep["program"]["losses"],
               "reference_losses": keep["reference"]["losses"],
               "leaves": {"program": _leaves(keep["program"]),
                          "reference": _leaves(keep["reference"])}}
        if i < control_seeds:
            cfg, mix = keep["cfg"], keep["mix"]
            ref = keep["reference"]
            mod = importlib.import_module("chipbench.reference."
                                          + cfg["family"])
            key = common.seed_key(seed)
            ctl = mod.readings(cfg, mix, key, keep["pool"], "fp8")[0]
            row["control"] = compare.gaps(ctl, ref)
            row["leaves"]["control"] = _leaves(ctl)
            half = mod.readings(cfg, mix, key,
                                [faults.halve(b) for b in keep["pool"]])[0]
            row["half"] = compare.gaps(half, ref)
        rows.append(row)
        log(row)
    summary = {}
    for n in rows[0]["program"]:
        lower = max(r["program"][n] for r in rows)
        s = {"lower": lower}
        for kind in ("control", "half"):
            vals = [r[kind][n] for r in rows if kind in r]
            if vals:
                s[kind] = min(vals)
                s[kind + "_over_lower"] = min(vals) / max(lower, 1e-30)
        summary[n] = s
    return rows, summary


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--out", help="also append every line to this file")
    a = p.parse_args(argv)

    def log(rec, tag="calibrate"):
        line = f"{tag} {json.dumps(rec)}"
        print(line, flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(line + "\n")

    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    _, summary = calibrate(a.workload, [int(s) for s in a.seeds.split(",")],
                           a.control_seeds, log=log)
    log(summary, "summary")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
