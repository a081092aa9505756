"""`chip_smoke.py` refuses to run anywhere but a TPU."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_off_tpu_and_names_the_platform(capsys):
    import jax

    assert _load().main([]) == 2
    out, err = capsys.readouterr()
    assert repr(jax.default_backend()) in err
    assert '"ok"' not in out


def test_checks_fail_loudly(capsys):
    checks = _load().Checks()
    assert checks("fine", True) and not checks("broken", False, "why")
    assert checks.failed == ["broken"]
    assert "[FAIL] broken: why" in capsys.readouterr().out
