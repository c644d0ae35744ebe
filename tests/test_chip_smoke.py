"""chip_smoke.py's checks at tiny size on the CPU.

The script refuses anything but a TPU, so these tests dress the CPU
device up as one and build the demo system in place of the paper's.
With the Mosaic-kernel check steered to pass, a clean run must print the
`ok` line; an injected step fault, or the real check finding no Pallas
custom call in a CPU-compiled step, must exit non-zero without it."""
import importlib.util
import json
import pathlib

import jax
import pytest

from repro.launch.serve import asr_demo_engine, asr_demo_system
from repro.serving.faults import FaultPolicy, FaultSpec

ROOT = pathlib.Path(__file__).resolve().parents[1]


class _AsTpu:
    """A CPU device that reports itself as a TPU."""
    platform = "tpu"
    device_kind = "cpu posing as tpu"

    def __init__(self, dev):
        self.id = dev.id

    def memory_stats(self):
        return None


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "build_system", lambda seed: asr_demo_system())
    monkeypatch.setattr(mod, "STREAMS", 3)
    monkeypatch.setattr(mod, "SLOTS", 4)
    monkeypatch.setattr(mod, "WORDS_PER_UTT", 2)
    monkeypatch.setattr("repro.runtime.compile_cache.use_persistent_cache",
                        lambda: "off")
    return mod


def _run(mod, capsys, argv=()):
    """main() as the interpreter would exit: an exception is status 1."""
    try:
        rc = mod.main(list(argv))
    except Exception:
        rc = 1
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, lines


def test_smoke_refuses_a_cpu(smoke, capsys):
    rc, lines = _run(smoke, capsys)
    assert rc == 2 and lines == []


@pytest.mark.parametrize("case", ["clean", "step_fault", "no_custom_call"])
def test_smoke_checks(smoke, capsys, monkeypatch, case):
    monkeypatch.setattr(smoke, "devices",
                        lambda: [_AsTpu(d) for d in jax.devices()])
    if case != "no_custom_call":
        monkeypatch.setattr(smoke, "step_kernels",
                            lambda engine: set(smoke.KERNELS))
    if case == "step_fault":
        # every step of the served phase fails (warm-up runs clean)
        armed = {"on": False}
        policy = FaultPolicy([FaultSpec(
            "asr_step", count=None, match=lambda ctx: armed["on"])])
        monkeypatch.setattr(
            smoke, "build_engine",
            lambda n, system, mesh=None: asr_demo_engine(
                n, mesh=mesh, system=system, faults=policy)[0])
        serve = smoke.serve_streams

        def faulty_serve(engine, utts):
            armed["on"] = True
            try:
                return serve(engine, utts)
            finally:
                armed["on"] = False
        monkeypatch.setattr(smoke, "serve_streams", faulty_serve)

    rc, lines = _run(smoke, capsys)
    if case == "clean":
        assert rc == 0
        assert json.loads(lines[-1]) == {"ok": True, "device": {
            "platform": "tpu", "kind": "cpu posing as tpu",
            "count": jax.device_count()}}
        return
    assert rc != 0
    assert not any(line.startswith('{"ok"') for line in lines)
    want = ("faulted sessions" if case == "step_fault"
            else "no Mosaic custom call")
    assert any(line.startswith("FAIL") and want in line for line in lines)


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_smoke_four_chips(smoke, capsys, monkeypatch):
    """--chips 4: the 2x2-mesh engine against the one-chip engine."""
    monkeypatch.setattr(smoke, "devices",
                        lambda: [_AsTpu(d) for d in jax.devices()])
    monkeypatch.setattr(smoke, "MESH_STREAMS", 2)
    rc, lines = _run(smoke, capsys, ["--chips", "4"])
    assert rc == 0
    assert json.loads(lines[-1])["device"]["count"] == jax.device_count()

