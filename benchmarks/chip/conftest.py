"""For the benchmark's CPU tests (`benchmarks/chip/tests`).

`test_harness_cpu.make_root` builds a tiny checkout whose one cell,
`tiny.bulk`, stands in for `edge8.bulk`: it renames each name in a
metric's `workloads` through a map that knows `edge8.bulk` alone, and
raises KeyError on any other cell.  Cells of other configurations (such
as `fconf8.bulk`) have no tiny twin there, so `make_root` is handed a
BENCHMARK.json without them: the tiny checkout is the one it builds
from the benchmark's own file when `edge8.bulk` is the only cell.
"""
import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parent
TWINNED = {"edge8.bulk"}            # the cells make_root's map renames

if str(CHIP / "tests") not in sys.path:
    sys.path.insert(0, str(CHIP / "tests"))

import test_harness_cpu  # noqa: E402

_make_root = test_harness_cpu.make_root


def make_root(tmp: Path) -> Path:
    """`test_harness_cpu.make_root` over a view of the repository whose
    BENCHMARK.json lists only the cells its map renames."""
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w in TWINNED]
    view = tmp.with_name(tmp.name + "-view")
    (view / "benchmarks").mkdir(parents=True)
    (view / "benchmarks" / "chip").symlink_to(CHIP, target_is_directory=True)
    (view / "BENCHMARK.json").write_text(json.dumps(bench))
    test_harness_cpu.CHIP = view / "benchmarks" / "chip"
    try:
        return _make_root(tmp)
    finally:
        test_harness_cpu.CHIP = CHIP


test_harness_cpu.make_root = make_root
