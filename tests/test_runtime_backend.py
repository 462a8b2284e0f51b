"""Backend decisions made in one place: Pallas interpret mode, the
persistent compile cache, XLA flags, device-kind matching and where the
wallclock executor builds its inputs."""
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.runtime import backend

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("given,expected", [(None, True), (True, True),
                                             (False, False)])
def test_interpret_mode_follows_backend(given, expected):
    """None interprets exactly where no TPU backend is present (here: the
    CPU); an explicit bool wins."""
    assert jax.default_backend() != "tpu"
    assert backend.interpret_mode(given) is expected


def _record_config_updates(monkeypatch):
    seen = {}
    monkeypatch.setattr(backend.jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    return seen


def test_compile_cache_env_dir_stands(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is JAX's own setting: no directory
    is set in code."""
    seen = _record_config_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    assert seen == {}


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    seen = _record_config_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = backend.enable_compile_cache()
    assert got == str(REPO / ".jax_cache") == str(backend.CACHE_DIR)
    assert seen == {"jax_compilation_cache_dir": got}
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_launcher_imports_leave_xla_flags():
    """Importing the launchers (and the dry-run module they once pulled in
    for default_plan) must not rewrite XLA_FLAGS; only dryrun's main()
    adds its placeholder devices, appended to what is there."""
    code = (
        "import os\n"
        "import repro.launch.train, repro.launch.serve, repro.launch.dryrun\n"
        "assert os.environ['XLA_FLAGS'] == '--xla_dump_to=x', "
        "os.environ['XLA_FLAGS']\n"
        "repro.launch.dryrun.force_host_devices()\n"
        "print(os.environ['XLA_FLAGS'])\n")
    env = dict(os.environ, XLA_FLAGS="--xla_dump_to=x", JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [
        "--xla_dump_to=x", "--xla_force_host_platform_device_count=512"]


def test_chip_smoke_refuses_without_tpu():
    """The chip smoke test never falls back to another backend: on the
    CPU it exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "no TPU" in out.stderr


@pytest.mark.parametrize("kind,matches", [("", True),
                                          ("TPU v5 lite", True),
                                          ("tpu", False)])
def test_meshspec_matches_device_kind(monkeypatch, kind, matches):
    """A spec's device_kind names the chip as JAX reports its kind, not
    its platform."""
    from repro.core.meshspec import MeshSpec
    chips = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")] * 4
    monkeypatch.setattr(jax, "devices", lambda *a: chips)
    got = MeshSpec.of(kind, data=2, model=2)._local_devices()
    assert got == (chips if matches else [])


def test_wallclock_inputs_built_under_their_shardings():
    """Each stand-in leaf is made directly with its sharding (None = the
    default device), integers as zeros and floats as small noise."""
    from repro.core.executor import _materialize
    mesh = jax.make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P("data"))
    args = ({"w": jax.ShapeDtypeStruct((4, 3), "bfloat16"),
             "ids": jax.ShapeDtypeStruct((4,), "int32")},
            jax.ShapeDtypeStruct((2,), "float32"))
    out = _materialize(args, (sh, None))
    w, ids, x = out[0]["w"], out[0]["ids"], out[1]
    assert w.sharding == sh and ids.sharding == sh
    assert (w.shape, w.dtype) == ((4, 3), np.dtype("bfloat16"))
    assert not np.asarray(ids).any()
    assert 0 < float(np.abs(np.asarray(w, np.float32)).max()) < 1
    assert x.shape == (2,) and x.sharding.device_set == {jax.devices()[0]}
