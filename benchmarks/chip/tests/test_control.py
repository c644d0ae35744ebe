"""The control (the reference one precision below, bfloat16, in the
program's place) comes out not correct, at a tiny size on the CPU: it
fails at least one of the compared numbers on every seed tried."""
import io
import json
from contextlib import redirect_stdout

from test_harness_cpu import make_root


def test_control_fails_a_number_on_every_seed(tmp_path, monkeypatch):
    import jax

    import control
    import run
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    root = make_root(tmp_path)
    out = io.StringIO()
    with redirect_stdout(out):
        control.main(["--workload", "tiny.bulk", "--seeds", "3,4,5"],
                     root=root)
    rows = json.loads(out.getvalue().strip().splitlines()[-1])["control"]
    assert len(rows) == 3
    assert all(r["fails"] for r in rows), rows
