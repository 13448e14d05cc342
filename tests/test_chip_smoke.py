"""chip_smoke.py never falls back to the CPU: off the TPU it exits
non-zero, names the platform it found, and prints no result line."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_the_cpu(monkeypatch, capsys):
    smoke = _load_chip_smoke()
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as e:
        smoke.main()
    assert "needs a TPU" in str(e.value.code) and "'cpu'" in str(e.value.code)
    assert capsys.readouterr().out == ""
