"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size (ResNet-8,
batch 8): the same study through the same entry points, held to the same
checks as on the chip — every execution path taken, every group run
batched, no kernel fallback, finite losses, and the forked-prefix
invariant.  The four-device phase runs on four virtual CPU devices."""

import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_smoke_study_takes_every_path():
    trainer = chip_smoke.build_trainer(n=1, batch=8)
    res = chip_smoke.run_study(trainer)
    assert chip_smoke.study_failures(trainer, res) == []
    st = res["stats"]
    assert st.batched_groups >= 2          # one group per promoted momentum
    assert st.chain_fused_stages > 0
    assert st.kernel_fallbacks == 0
    assert {d.platform for devs in trainer.placed.values()
            for d in devs} == {jax.default_backend()}
    assert chip_smoke.forked_prefix_error(trainer, res) \
        <= chip_smoke.PARAM_RTOL


def test_smoke_refuses_to_run_without_a_tpu(capsys):
    """No CPU mode: without a TPU the script fails and prints no result."""
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


_FLEET = """
import sys
sys.path.insert(0, {repo!r})
import jax
assert jax.device_count() == 4, jax.device_count()
import chip_smoke
ways = chip_smoke.fleet_ways(0, n=1, batch=8)
bad = chip_smoke.fleet_failures(ways)
assert not bad, bad
print("FLEET-OK")
"""


def test_smoke_fleet_phase_on_four_virtual_devices(tmp_path):
    """``--chips 4``'s three ways (one device, four 1-device workers, one
    4-device worker) agree and keep every state on the owning worker's
    devices.  Subprocess: the forced device count precedes jax import."""
    script = tmp_path / "fleet.py"
    script.write_text(_FLEET.format(repo=REPO))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "FLEET-OK" in proc.stdout
