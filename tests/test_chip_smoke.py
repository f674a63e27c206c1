"""``chip_smoke.py``'s checks rehearsed on the CPU: the kernel parity phase
in interpret mode, and the islands-against-vectorized comparison on four
virtual devices (bitwise equal there, so every ratio is 0)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ISLANDS_PROBE = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke
argv = ["--algo", "td3", "--env", "hopper2d", "--population", "8",
        "--strategy", "none", "--batch", "32", "--num-envs", "4",
        "--collect-steps", "8", "--updates-per-iter", "4", "--steps", "2",
        "--eval-every", "2", "--resume", "none"]
chip_smoke.islands_against_vectorized({ckpt!r}, argv=argv)
"""


def test_kernels_against_reference_in_interpret_mode():
    sys.path.insert(0, REPO)
    import chip_smoke
    chip_smoke.kernels_against_reference()


def test_islands_against_vectorized_on_four_cpu_devices(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c",
         ISLANDS_PROBE.format(repo=REPO, ckpt=str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    line, = [l for l in r.stdout.splitlines()
             if "islands x4 vs vectorized x1" in l]
    assert "max abs diff 0.0;" in line
    assert "|islands - vectorized| / |vectorized - init|: max 0.0," in line
