"""Guards that keep a run from hiding the device it ran on (ISSUE 22): the
compile-cache resolver, the interpret-mode refusal on a TPU backend, the
TPU memory_stats contract, the launcher's one-process-per-chip check, and
chip_smoke.py's refusal to run anywhere but a TPU. Tiny and CPU-only — the
chip path itself is proved by `python chip_smoke.py` on the chip.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "PADDLE_TPU_PALLAS_INTERPRET")}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu", **extra)
    return env


class TestCompileCachePlacement:
    def test_env_set_means_nothing_set_in_code(self, monkeypatch, tmp_path):
        from paddle_tpu.framework.compile_cache import place_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_is_one_fixed_path_under_the_checkout(self, tmp_path):
        """Two fresh processes, started from different directories, land on
        the same directory inside the checkout — the path is part of the
        cache key, so a directory that moves never hits."""
        code = ("import jax; "
                "from paddle_tpu.framework.compile_cache import "
                "place_compile_cache as p; d = p(); "
                "assert jax.config.jax_compilation_cache_dir == d; print(d)")
        procs = [subprocess.Popen([sys.executable, "-c", code], cwd=cwd,
                                  env=_clean_env(), text=True,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE)
                 for cwd in (str(tmp_path), _REPO)]
        outs = [p.communicate(timeout=120) for p in procs]
        assert all(p.returncode == 0 for p in procs), outs
        dirs = {o.strip().splitlines()[-1] for o, _e in outs}
        assert dirs == {os.path.join(_REPO, ".jax_cache")}


class TestInterpretModeIsCpuOnly:
    def test_tpu_backend_refuses_the_variable(self, monkeypatch):
        from paddle_tpu.ops import pallas

        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        assert pallas.interpret_mode() and pallas.kernels_available()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="PADDLE_TPU_PALLAS_INTERPRET"):
            pallas.interpret_mode()
        with pytest.raises(RuntimeError, match="PADDLE_TPU_PALLAS_INTERPRET"):
            pallas.kernels_available()
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
        assert not pallas.interpret_mode() and pallas.kernels_available()

    def test_backend_failure_is_not_a_composite_fallback(self, monkeypatch):
        from paddle_tpu.nn.functional.flash_attention import _use_pallas_kernel
        from paddle_tpu.ops.pallas import kernels_available

        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
        assert not _use_pallas_kernel() and not kernels_available()  # bare CPU

        def dead():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "default_backend", dead)
        for gate in (_use_pallas_kernel, kernels_available):
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                gate()


def test_tpu_without_memory_stats_is_an_error():
    import paddle_tpu as paddle

    class _Dev:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

        def memory_stats(self):
            return None

    assert paddle.device.memory_stats().get("synthesized")  # CPU convenience
    with pytest.raises(RuntimeError, match="no memory_stats"):
        paddle.device.memory_stats(_Dev())


class TestLauncherOneProcessPerChip:
    def test_multi_worker_refused_where_workers_would_take_the_tpu(
            self, monkeypatch):
        from paddle_tpu.distributed.launch import main as launch

        monkeypatch.setattr(launch.glob, "glob",
                            lambda pat: ["/dev/accel0"] if "accel" in pat
                            else [])
        assert launch._workers_would_use_tpu({})
        assert launch._workers_would_use_tpu({"JAX_PLATFORMS": "tpu,cpu"})
        assert not launch._workers_would_use_tpu({"JAX_PLATFORMS": "cpu"})
        with pytest.raises(RuntimeError, match="one worker per host"):
            launch._check_one_process_per_chip(2, {})

    def test_launcher_with_a_live_backend_refuses_to_spawn(self):
        from paddle_tpu.distributed.launch import main as launch

        jax.devices()  # this process holds a backend now
        with pytest.raises(RuntimeError, match="already initialized"):
            launch._check_one_process_per_chip(1, {"JAX_PLATFORMS": "cpu"})


class TestChipSmokeRefusals:
    def _run(self, **env):
        return subprocess.run(
            [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
            env=_clean_env(**env), capture_output=True, text=True,
            timeout=120)

    def test_no_tpu_exits_nonzero_naming_the_platform(self):
        r = self._run()
        assert r.returncode != 0
        assert "platform is 'cpu'" in r.stderr and "'tpu'" in r.stderr
        assert r.stdout.strip() == ""  # no result line

    def test_interpret_variable_refused(self):
        r = self._run(PADDLE_TPU_PALLAS_INTERPRET="1")
        assert r.returncode != 0
        assert "PADDLE_TPU_PALLAS_INTERPRET" in r.stderr
        assert r.stdout.strip() == ""

    def test_last_line_is_exactly_ok_and_device(self):
        # the driver refuses any other key on the last line of stdout; the
        # per-phase report is the line before it
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        devs = jax.devices()
        doc = json.loads(mod.verdict_line(True, devs))
        assert doc == {"ok": True,
                       "device": {"platform": devs[0].platform,
                                  "kind": devs[0].device_kind,
                                  "count": len(devs)}}
        assert json.loads(mod.verdict_line(False, devs))["ok"] is False
