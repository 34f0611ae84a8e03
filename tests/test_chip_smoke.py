"""`chip_smoke.py` on the CPU: the rehearsal of its control flow.

`--tiny` runs the same phases through the same entry points (a JaxTrainer
worker fed by `iter_jax_batches`; an LLMDeployment behind the HTTP proxy,
streamed, unary, redeployed) with the tiny model on CPU workers that
pretend to own one `TPU` resource.  It must print its phases and then
end non-zero at the device check — it never says `"ok": true` without a
chip.  Without `--tiny` it must fail before any phase: detection finds
no chip here.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout, devices=1):
    # One CPU device stands for one chip.
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=REPO)


def test_tiny_rehearsal_runs_phases_then_fails_device_check():
    out = _run("--tiny", timeout=420)
    phases = {}
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            obj = json.loads(line)
            assert "ok" not in obj, line
            phases[obj["phase"]] = obj
    assert out.returncode != 0, out.stdout[-2000:]
    assert "device check" in out.stderr, out.stderr[-2000:]
    assert set(phases) == {"train", "serve"}, out.stderr[-2000:]

    train, serve = phases["train"], phases["serve"]
    assert train["platform"] == serve["platform"] == "cpu"
    assert len(train["losses"]) == 6 and len(train["step_s"]) == 5
    assert serve["stream_tokens"] == [serve["new_tokens"]] * 4
    assert serve["streamed_equals_unary"] and serve["rails_used"]
    # One owner per "chip": the redeployed replica is a new process that
    # started only after the old one was gone.
    assert serve["redeploy"]["new_replica"]
    assert serve["redeploy"]["old_replica_exit_wait_s"] < 1.0


def test_tiny_four_chip_rehearsal_compares_sharded_with_single_device():
    """`--chips 4 --tiny`: only the sharded-training comparison runs, in
    one worker that owns all four "chips"; the checks that need no TPU
    (losses agree, parameters and optimizer state split four ways) pass,
    and the run still ends non-zero at the device check."""
    out = _run("--tiny", "--chips", "4", timeout=300, devices=4)
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    assert out.returncode != 0, out.stdout[-2000:]
    assert "device check" in out.stderr, out.stderr[-2000:]
    assert [obj.get("phase") for obj in lines] == ["train_sharded"]
    r = lines[0]
    sh, one = r["sharded"], r["single"]
    assert r["count"] == 4
    assert len(sh["losses"]) == len(one["losses"]) == 3
    assert sh["wq_shard_shape"][1] * 4 == sh["wq_shape"][1]
    assert one["wq_shard_shape"] == one["wq_shape"]
    assert len(sh["opt_state_bytes_on_device"]) == 4
    assert max(sh["opt_state_bytes_on_device"]) * 3.9 < sh["opt_state_bytes"]
    assert one["opt_state_bytes_on_device"] == [one["opt_state_bytes"]]
    assert sh["argument_bytes_per_device"] * 3.5 < one[
        "argument_bytes_per_device"]


def test_without_a_chip_nothing_runs_and_no_result_is_printed():
    out = _run(timeout=180)
    assert out.returncode != 0
    assert "no chip was detected" in out.stderr, out.stderr[-2000:]
    assert not any(line.startswith("{") for line in
                   out.stdout.splitlines()), out.stdout[-2000:]


def test_compile_cache_dir_is_the_environment_or_one_fixed_path(monkeypatch):
    """`JAX_COMPILATION_CACHE_DIR` set: used as is, nothing else set in
    code.  Unset: one fixed, git-ignored directory of the checkout,
    exported so that every child process agrees (never a temp name)."""
    import jax

    from ray_tpu.util import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/somewhere/else")
        assert compile_cache.configure() == "/somewhere/else"
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV)
        fixed = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure() == fixed
        assert compile_cache.configure() == fixed       # and stays there
        assert os.environ[compile_cache.CACHE_DIR_ENV] == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
