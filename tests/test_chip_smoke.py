"""chip_smoke.py off the chip: it refuses a machine without a TPU, and its
one-chip path runs end to end at smoke size on the CPU."""
import argparse
import importlib.util
import os
import pathlib
import subprocess
import sys

from repro import configs
from repro.launch import serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_one_chip_path_at_smoke_size(monkeypatch, capsys):
    """The chip path with every config swapped for its smoke twin: two
    batches through serve.main, cache counts, finite finals, and the
    int8-vs-f32 reference check."""
    cs = _chip_smoke()
    full = configs.get_config
    monkeypatch.setattr(configs, "get_config",
                        lambda arch, smoke=False: full(arch, smoke=True))
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    cs.one_chip(argparse.Namespace(steps=3, seed=0))
    out = capsys.readouterr().out
    assert "compiled-sampler cache: 2 traces, 2 hits" in out
    assert "reference: int8 DiT forward vs f32 forward" in out
