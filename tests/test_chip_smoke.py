"""The rules ``chip_smoke.py`` and the bring-up path hold themselves to,
checked without a chip: where the compile cache goes, that the smoke's
parent stays off JAX, that no accelerator means no ``"ok": true``, that a
native binary is keyed on its source's content, and that the control-plane
processes never initialise a backend (a chip belongs to one process)."""

import ctypes
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from distributed_tensorflow_tpu.utils import backend as backend_lib
from distributed_tensorflow_tpu.utils.native import build_and_load

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_py(args, *, cwd=REPO, timeout=120, **env_extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture
def cache_config():
    """configure_backend writes process-wide config: put it back."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert backend_lib.configure_backend() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = (backend_lib.configure_backend(),
                     backend_lib.configure_backend())
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_importing_chip_smoke_leaves_jax_unimported():
    proc = run_py(["-c", "import sys, chip_smoke; "
                   "bad = {'jax', 'distributed_tensorflow_tpu'} "
                   "& set(sys.modules); assert not bad, bad"])
    assert proc.returncode == 0, proc.stderr


def test_chip_smoke_without_an_accelerator_fails_and_prints_no_ok():
    proc = run_py([SMOKE], timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "need a TPU" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program must not pass."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    proc = run_py([str(alone), "--rehearse"], cwd=tmp_path)
    assert proc.returncode not in (0, 4)
    assert '"ok"' not in proc.stdout


def test_native_binary_is_keyed_on_source_content_not_mtime(tmp_path):
    src = tmp_path / "answer.cc"
    lib = str(tmp_path / "libanswer.so")

    def answer(value: int) -> int:
        src.write_text(f'extern "C" int answer() {{ return {value}; }}\n')
        os.utime(src, (1, 1))   # the source looks older than any binary
        fn = build_and_load(lib, str(src)).answer
        fn.restype = ctypes.c_int
        return fn()

    assert answer(1) == 1
    assert answer(2) == 2   # rebuilt, though the first binary is newer
    assert answer(1) == 1   # and the first binary is found again, not rebuilt
    assert len(list(tmp_path.glob("libanswer.*.so"))) == 2


def test_control_plane_processes_never_initialise_a_backend(tmp_path):
    """The PS role (``TpuServer``), ``tools/coord_shard`` and both routers
    import the package — hence JAX — but must leave the chip to the process
    that computes.  (``chip_smoke.py``'s ``train_cli`` phase proves the
    same of the real PS process, on the chip.)"""
    proc = run_py(["-c", textwrap.dedent(f"""
        import time
        from distributed_tensorflow_tpu.cluster.server import TpuServer
        from distributed_tensorflow_tpu.cluster.spec import ClusterSpec
        from distributed_tensorflow_tpu.serving.cells import GlobalRouter
        from distributed_tensorflow_tpu.serving.router import Router
        from distributed_tensorflow_tpu.tools import serve_cell, serve_fleet
        from distributed_tensorflow_tpu.tools.coord_shard import (
            launch_instances)

        servers, _ = launch_instances(
            port=0, instances=2, num_tasks=1, heartbeat_timeout=5.0,
            persist_dir={str(tmp_path)!r}, host="127.0.0.1")
        ps = TpuServer(ClusterSpec({{"ps": "localhost:0",
                                    "worker": "localhost:0"}}), "ps", 0)
        routers = [Router(poll_s=0.05), GlobalRouter(poll_s=0.05)]
        for r in routers:
            r.start()
        time.sleep(0.3)   # a few control-loop ticks
        for r in routers:
            r.shutdown()
        ps.shutdown()
        for s in servers:
            s.stop()

        from jax._src import xla_bridge
        assert not xla_bridge.backends_are_initialized()
        """)])
    assert proc.returncode == 0, proc.stderr
