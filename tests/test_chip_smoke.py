"""``chip_smoke.py``'s phases at smoke size on the CPU (kernels interpreted),
the projection-kernel pin for a skip-listed model, its refusal to run
without a TPU or outside the repo, and the compile-cache helper."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.analysis.jaxpr_utils import count_pallas_calls
from repro.configs.base import get_smoke_config

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, _ROOT)
import chip_smoke as cs  # noqa: E402

_SMOKE_ONE = cs.Workload(lengths=(40, 24, 33), new_tokens=4, chunk=16,
                         max_seq=64, slots=2, block_size=8, shared=16,
                         late=True, compare_len=32)
_SMOKE_FOUR = cs.Workload(lengths=(24, 16, 20, 12), new_tokens=3, chunk=16,
                          max_seq=32, slots=2, block_size=8)


@pytest.fixture(scope="module")
def smoke_cfg():
    return get_smoke_config("qwen2_7b")


def test_one_chip_phases_at_smoke_size(smoke_cfg, capsys):
    """The whole one-chip driver: serving through the facade (all done,
    no degradation, one trace per bucket), one projection kernel per
    pruned projection in the prefill step, kernels on vs off in tolerance."""
    cs.one_chip(smoke_cfg, _SMOKE_ONE)
    out = capsys.readouterr().out
    assert "prefill step program: 3 projection kernels for 3" in out
    assert "prefix_hits=2" in out
    assert "float32 2-layer prefill logits, kernels on vs off" in out


@pytest.mark.parametrize("kernels", [True, False], ids=["on", "off"])
def test_skip_listed_prefill_one_kernel_per_pruned_projection(smoke_cfg,
                                                              kernels):
    """Scan-stacked qwen2 with q/gate skip layers: with kernels on, each
    pruned projection (q_proj, gate_proj via their layer flag, down_proj)
    lowers to exactly one pallas_call; with kernels off, none."""
    model, params = cs.build(smoke_cfg, cs.amber_policy(smoke_cfg))
    policy = cs.amber_policy(smoke_cfg, kernels)
    tokens = jax.numpy.zeros((1, 16), jax.numpy.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: model.forward(
        p, {"tokens": t}, policy=policy, phase="prefill"))(params, tokens)
    assert cs.pruned_projections(smoke_cfg, policy) == 3
    assert count_pallas_calls(jaxpr) == (3 if kernels else 0)


def test_projection_kernel_count_reads_hlo():
    line = ('  %nm_prune_matmul_pallas.3 = bf16[8,8] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    other = ('  %paged_attention_pallas.1 = bf16[8,8] custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    assert cs.projection_kernel_count("\n".join([line, other, line])) == 2


_FOUR = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import chip_smoke as cs
    from repro.configs.base import get_smoke_config
    wl = cs.Workload(**{wl!r})
    cs.four_chips(get_smoke_config("qwen2_7b"), wl)
""")


def test_four_chip_phase_on_fake_devices():
    """dp=4 and tp=4 against dp=1/tp=1 on four fake CPU devices: tokens
    identical, each dp replica's params and cache on its own device,
    float32 tp logits in tolerance (the exit code)."""
    import dataclasses
    code = _FOUR.format(root=_ROOT, wl=dataclasses.asdict(_SMOKE_FOUR))
    env = {**os.environ, "PYTHONPATH": os.path.join(_ROOT, "src"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "dp=4 tp=1 tokens identical to dp=1 tp=1: True" in proc.stdout
    assert ("dp=1 tp=4 greedy tokens equal to dp=1 tp=1 (information): "
            "1.000") in proc.stdout
    assert ("float32 cut, tp=4 greedy tokens equal to tp=1 (information): "
            "1.000") in proc.stdout
    assert "prefill logits, tp=4 vs tp=1: max|diff|=" in proc.stdout
    replicas = [ln for ln in proc.stdout.splitlines() if "replica " in ln]
    assert len(replicas) == 4 and len(set(replicas)) == 4, proc.stdout


def _last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_main_refuses_without_a_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(_ROOT,
                                                         "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert _last_json(proc.stdout) is None
    assert "needs a TPU" in proc.stderr


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=str(tmp_path))
    assert proc.returncode != 0
    assert _last_json(proc.stdout) is None


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the helper sets nothing."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import setup_compile_cache\n"
            "print(setup_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
    env = {**os.environ, "PYTHONPATH": os.path.join(_ROOT, "src"),
           "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path)] * 2
    assert os.listdir(tmp_path)


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch.compile_cache import setup_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = setup_compile_cache()
        assert path == os.path.join(_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    out = subprocess.run(["git", "check-ignore", "-q", ".jax_cache"],
                         cwd=_ROOT)
    assert out.returncode == 0, ".jax_cache must be git-ignored"
