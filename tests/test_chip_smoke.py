"""chip_smoke.py off the chip, and where the compile cache goes.

The chip itself is out of reach here; what the CPU can check is that the
script rehearses end to end when asked to, refuses to produce a result
without a chip when not, and that the package puts JAX's persistent
compile cache where the environment says — or, unset, at one fixed place
in the checkout whatever the working directory.
"""
import json
import os
import shutil
import subprocess
import sys

import jax

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(REPO, "chip_smoke.py")
DEFAULT_CACHE = os.path.join(REPO, ".jax_cache")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _checkout_of_its_own(tmp_path):
    """The script copied, the package and the native sources linked: the
    package finds its default cache beside where it was imported from, so
    this checkout's ``.jax_cache`` is a directory that no other test's
    process writes (the real checkout's is every worker's)."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(SMOKE, root / "chip_smoke.py")
    for name in ("incubator_mxnet_tpu", "src"):
        os.symlink(os.path.join(REPO, name), root / name)
    return root


def test_rehearsal_runs_and_caches_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    checkout = _checkout_of_its_own(tmp_path)
    r = subprocess.run(
        [sys.executable, str(checkout / "chip_smoke.py"), "--rehearse"],
        cwd=str(tmp_path),
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the last line holds the verdict and the device, and no other key
    last = json.loads(lines[-1])
    assert sorted(last) == ["device", "ok"] and last["ok"] is True
    assert sorted(last["device"]) == ["count", "kind", "platform"]
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    # the facts of the run are the line before it
    assert lines[-2].startswith("[result] ")
    out = json.loads(lines[-2][len("[result] "):])
    assert out["ok"] is True and out["rehearsal"] is True
    assert out["device"] == last["device"]
    # the Pallas phases are named, and named as skipped
    assert out["flash_attention"] == "skipped: no chip"
    assert out["transformer_lm"] == "skipped: no chip"
    # and the on-chip check of the bit generator's masks with them
    assert out["dropout_masks"] == "skipped: no chip"
    assert out["resnet"]["losses"][-1] < out["resnet"]["losses"][0]
    assert out["second_compile"]["second_step_from_cache"] is True
    # the cache went where the environment said and nowhere else
    assert out["compile_cache"]["compile_cache_dir"] == str(cache)
    assert out["compile_cache"]["compile_cache_dir_from_env"] is True
    assert os.listdir(cache)
    assert not (checkout / ".jax_cache").exists()


def test_no_chip_no_result():
    r = subprocess.run([sys.executable, SMOKE], env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""      # no metric, no JSON line


def test_default_cache_is_in_the_checkout_whatever_the_cwd(tmp_path):
    # this process: pytest's own working directory
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import incubator_mxnet_tpu  # noqa: F401 - applies the cache config
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE
    # a second process, started somewhere else
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax, incubator_mxnet_tpu; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=str(tmp_path), env=_env(PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == DEFAULT_CACHE
