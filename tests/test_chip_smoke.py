"""chip_smoke.py's host-side contract: where the compilation cache goes,
and that a run without a TPU fails before doing anything.  Neither test
enables the cache or touches an accelerator."""
import importlib.util
import pathlib

import pytest

from repro.launch.compile_cache import ENV, compile_cache_dir

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("environ,expect", [
    ({ENV: "/elsewhere/cache"}, None),           # JAX reads it itself
    ({}, ".jax_cache"),
    ({ENV: ""}, ".jax_cache"),                   # empty means unset
], ids=["env_set", "env_unset", "env_empty"])
def test_compile_cache_dir(tmp_path, environ, expect):
    got = compile_cache_dir(tmp_path, environ)
    if expect is None:
        assert got is None
    else:
        assert got == tmp_path.resolve() / expect


def test_no_tpu_exits_nonzero_and_names_platform(capsys):
    import jax
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    platform = jax.devices()[0].platform
    if platform == "tpu":
        pytest.skip("a TPU is attached")
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert repr(platform) in err
    assert out == ""
