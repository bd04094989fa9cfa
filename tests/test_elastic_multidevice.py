"""Elastic checkpoint/restart across DIFFERENT mesh shapes, on real
(placeholder) multi-device meshes. Runs in a subprocess because jax locks
the device count at first init and the main test process must stay
single-device."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.multidevice]

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, "src")
from repro import ckpt
from repro.dist import sharding as sh
from repro.utils import meshctx
from repro.launch.mesh import auto_mesh

tmp = sys.argv[1]

# --- phase 1: "train" on a (4, 2) mesh, save sharded state ---
mesh_a = auto_mesh((4, 2), ("data", "model"))
w = jnp.arange(64.0, dtype=jnp.float32).reshape(8, 8)
sh_a = NamedSharding(mesh_a, P("data", "model"))
w_a = jax.device_put(w, sh_a)

@jax.jit
def step(w):
    return w * 1.5 + 1.0

w_a = step(w_a)
ckpt.save(tmp, 1, {"w": w_a})

# --- phase 2: restore onto a (2, 4) mesh (elastic reshard) ---
mesh_b = auto_mesh((2, 4), ("data", "model"))
sh_b = NamedSharding(mesh_b, P("data", "model"))
like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
restored, meta = ckpt.restore(tmp, like, shardings={"w": sh_b})
w_b = step(restored["w"])

expect = (np.arange(64.0).reshape(8, 8) * 1.5 + 1.0) * 1.5 + 1.0
ok_values = bool(np.allclose(np.asarray(w_b), expect))
ok_shard = restored["w"].sharding.is_equivalent_to(sh_b, 2)
print(json.dumps({"ok_values": ok_values, "ok_shard": bool(ok_shard),
                  "ndev": jax.device_count()}))
"""


def test_elastic_reshard_across_meshes(tmp_path):
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(tmp_path)],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ndev"] == 8
    assert res["ok_values"], res
    assert res["ok_shard"], res
