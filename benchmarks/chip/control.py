"""The control of a cell's `correct`: the plain reference put in the
program's place and computed one precision below what the configuration
states (`limits/<family>.json` "control": for float32 at "highest", the
acoustic model's matmuls at "high", three bfloat16 passes; the search
stays in float64), compared with the float32 reference by the numbers a
run compares, on the utterances a run of that seed would sample.

    python3 benchmarks/chip/control.py --workload edge8.bulk \
        --seeds 11,12,13

One line per seed with the control's numbers beside the limits, then a
JSON summary.  The program's readings for the limits come from the
benchmark's own runs; those runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness
import traffic as tr
from audio import utterance


def sample_lengths(cell, seed: int) -> list:
    """(index, seconds) of the utterances a run of `seed` would compare:
    the longest the mix offers, then others drawn from the seed."""
    served = [{"index": i, "seconds": s}
              for i, s in enumerate(tr.bulk_lengths(cell.mix, seed))]
    return harness.check_sample({"served": served}, cell.limits["sample"],
                                seed)


def main(argv=None, root=harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    harness.require_chips(cell.workload["chips"])
    from repro.runtime import compile_cache
    compile_cache.use_persistent_cache()
    harness.apply_precision(cell.cfg)
    model = cell.model()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        system = model.System(cell.cfg, seed)
        sample = sample_lengths(cell, seed)
        audios = [utterance(seed, s["index"], s["seconds"]) for s in sample]
        n_win, n_rows = cell.limits["reference_windows"], len(sample)

        def decode(dtype, precision):
            r = model.Reference(system, dtype, precision)
            return [r.decode(lp)
                    for lp in r.log_probs(audios, n_win, n_rows)]

        beams = decode("float32", "highest")
        ctl = cell.limits["control"]
        low = [model.best_of(b)
               for b in decode(ctl["dtype"], ctl["precision"])]
        nums = model.compare(low, beams)
        limits = cell.limits["limits"]
        fails = [k for k, v in limits.items() if nums[k] > v]
        print(f"control seed {seed}: " + ", ".join(
            f"{k} {v}" + (f" (limit {limits[k]})" if k in limits else "")
            for k, v in nums.items())
            + f"; fails {fails or 'nothing'}; "
            f"{sum(s['seconds'] for s in sample):.3f} s of audio, "
            f"{time.monotonic() - t:.3f}s", flush=True)
        rows.append({"seed": seed, **nums, "fails": fails})
    print(json.dumps({"workload": args.workload, "control": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
