"""The control of ``correct``: the plain reference computed at float8 (the
nearest serving precision below the configuration's bfloat16) in the
program's place, at the cell's own size. It has to come out NOT correct;
its readings set the upper end of each limit (PERF.md section 2). Run on
the chip when a configuration's limits are set; a benchmark run never runs
it. The same comparison at a small size is kept as a test
(``tests/test_reference.py``).

    python3 benchmark/control.py --workload clip64_1080p --seeds 11,12,13
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from vbench import correct, loader, weights     # noqa: E402
from vbench import traffic as traffic_mod       # noqa: E402


def first_window(model: dict, first: int, step: int = 37) -> list:
    """The first window the model's family gives a camera that reads frames
    ``first``, ``first + step``, ...: the frames of a control sample."""
    fam = loader.family(model["family"])
    reads = []
    while len(reads) < 4096:
        reads.append(first + step * len(reads))
        window = fam.window({"packet": reads[-1]}, reads, model["sizes"])
        if window:
            return window
    raise SystemExit(f"family {model['family']!r} gives no window")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--per-model", type=int, default=32)
    args = ap.parse_args(argv)
    import jax

    cell = loader.cell(args.workload)
    role_model = cell["config"]["roles"]
    models = {m["registry_model"]: m for m in loader.models(cell["config"])}
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        cams = traffic_mod.cameras(cell["traffic"], seed)
        flat = {name: weights.generate(seed, m["family"], m["sizes"], salt)
                for salt, (name, m) in enumerate(models.items())}
        # as many results per model as a run compares, of the cell's own
        # cameras and frame numbers
        sample = []
        for c in cams:
            name = role_model[c[2]]
            if sum(1 for r in sample if r["model"] == name) < args.per_model:
                sample.append({"device_id": c[1], "model": name,
                               "window": first_window(models[name],
                                                      9 + c[0] % 5)})
        exact = correct.reference_rows(sample, cams, seed, models, flat,
                                       loader.reference)
        low = correct.reference_rows(sample, cams, seed, models, flat,
                                     loader.reference, quant="fp8")
        names = [r["model"] for r in sample]
        numbers = correct.compare(
            [loader.family(models[n]["family"]).as_served(r)
             for n, r in zip(names, low)], exact, names, models)
        # judged on the numbers this comparison gives, and on no other
        limits = {k: v for k, v in cell["config"]["limits"].items()
                  if k in numbers}
        ok, checks = correct.verdict(numbers, limits)
        print(json.dumps({"seed": seed, "control": "fp8", "correct": ok,
                          "n": len(sample), "failed_limits": sorted(
                              k for k, c in checks.items()
                              if not c["value"] <= c["limit"]),
                          **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
