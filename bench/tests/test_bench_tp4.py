"""The four-chip path at a tiny size on four CPU devices, in a process of
its own (the device count is fixed when JAX starts): the program's
tensor-parallel step agrees with the sharded reference, and the reference
with the exchange between chips left out does not."""
import json
import os
import subprocess
import sys
from pathlib import Path

from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = """
import json, sys
from pathlib import Path
from bench import control, run
root = Path(sys.argv[1])
run.main(["--workload", "tiny.tp4", "--seed", "7", "--seconds", "0.5",
          "--trace", "0"], root=root, look_for_chip=False)
control.main(["--workload", "tiny.tp4", "--seeds", "7", "--modes",
              "exchange"], root=root, look_for_chip=False)
"""


def test_four_devices(tmp_path):
    root = tiny.write(tmp_path / "tree")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(root)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=280)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    bench, exchange = lines
    assert bench["device"]["count"] == 4
    assert bench["correct"] is True, bench["checks"]
    assert exchange["mode"] == "exchange" and exchange["correct"] is False
