"""Dry-run machinery on a small fake-device mesh (subprocess isolated —
device count locks at first jax init). One representative arch per family
x one shape per kind keeps CI tractable; the full 10x4x2 sweep is
``python -m repro.launch.dryrun --all`` (results in experiments/dryrun)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_step
from repro.launch.hlo_utils import collective_bytes, cost_summary

arch, shape = sys.argv[1], sys.argv[2]
mesh = make_host_mesh(4, 2)
built = build_step(arch, shape, mesh)
assert built is not None
with jax.set_mesh(mesh):
    lowered = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                      out_shardings=built["out_shardings"]).lower(*built["args"])
    compiled = lowered.compile()
ma = compiled.memory_analysis()
assert ma is not None and ma.argument_size_in_bytes > 0
cs = cost_summary(compiled)
assert cs["flops"] > 0
cb = collective_bytes(compiled.as_text())
print("DRYRUN-OK", cs["flops"], cb["total"])
"""


def _run(arch, shape):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", CODE, arch, shape],
                         capture_output=True, text=True, env=env, cwd=REPO,
                         timeout=1200)
    assert "DRYRUN-OK" in out.stdout, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("arch,shape", [
    ("qwen2_1_5b", "train_4k"),          # dense train
    ("dbrx_132b", "decode_32k"),         # MoE decode (EP small-T path)
    ("rwkv6_3b", "long_500k"),           # ssm long-context decode
    ("recurrentgemma_2b", "prefill_32k"),  # hybrid prefill
    ("whisper_medium", "train_4k"),      # enc-dec train
    ("minicpm3_4b", "decode_32k"),       # MLA absorbed decode
])
def test_dryrun_lowers_small_mesh(arch, shape):
    _run(arch, shape)


def test_production_dryrun_artifacts_exist():
    """The committed artifact sweep must cover every (arch x shape) on the
    single-pod mesh with ok/skipped status (run via launch.dryrun --all)."""
    d = os.path.join(REPO, "experiments", "dryrun")
    if not os.path.isdir(d) or len(os.listdir(d)) < 40:
        pytest.skip("full dry-run sweep artifacts not present")
    bad = []
    for f in os.listdir(d):
        if f.endswith("_pod256.json"):
            r = json.load(open(os.path.join(d, f)))
            if r["status"] not in ("ok", "skipped"):
                bad.append((f, r.get("error", "")[:100]))
    assert not bad, bad
