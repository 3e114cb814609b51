"""``chip_smoke.py`` reports only runs it made on a chip: off a TPU it exits
non-zero with nothing on stdout, and its tiny-size rehearsal is refused on a
TPU, so the contract line naming one can only end a full-size run."""
import importlib.util
import os
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    import repro.compile_cache
    # keep the persistent compile cache off in the test process
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_tpu(monkeypatch):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_no_tpu_exits_without_a_result(smoke, argv, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this process has a TPU")
    with pytest.raises(SystemExit) as e:
        smoke.main(argv)
    assert e.value.code != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


@pytest.mark.parametrize("argv", [["--rehearse"],
                                  ["--rehearse", "--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_rehearsal_refused_on_a_tpu(smoke, argv, monkeypatch, capsys):
    _fake_tpu(monkeypatch)
    with pytest.raises(SystemExit) as e:
        smoke.main(argv)
    assert e.value.code != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "--rehearse" in err
