"""Phase-timed replica of __graft_entry__.dryrun_multichip's child.

Run WITHOUT the parent wrapper:
    python tools/profile_dryrun.py [n_devices]
Sets the parent's env (CPU platform, fp cpu path) and times
build/trace/lower/compile/run separately.  No persistent cache.  XLA
flags beyond the device count come from PROFILE_XLA_EXTRA (empty =
XLA defaults) — pass the production child's flags explicitly when
predicting its compile behavior; __graft_entry__ is the source of truth
for what ships.
"""
import os
import sys
import time

n = int(sys.argv[1]) if len(sys.argv) > 1 else 8

# before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["LODESTAR_TPU_FP_PLATFORM"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={n} "
    + os.environ.get("PROFILE_XLA_EXTRA", "")
).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

t0 = time.time()
import __graft_entry__ as g
from lodestar_tpu.ops.bls12_381 import curve as _cv, verify as dv

(pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active), _ = g._example_batch(n)
rand = [(2 * i + 3) | 1 for i in range(n)]
bits = _cv.scalars_to_bits(rand, 16)
t1 = time.time()
print(f"build: {t1-t0:.1f}s", flush=True)

devices = jax.devices("cpu")[:n]
mesh = Mesh(devices, ("sp",))
shard = NamedSharding(mesh, P("sp"))
args = jax.tree.map(
    lambda x: jax.device_put(x, shard),
    (pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, bits, active),
)
jfn = jax.jit(dv.verify_signature_sets)
t2 = time.time()
traced = jfn.trace(*args)
t3 = time.time()
print(f"trace: {t3-t2:.1f}s", flush=True)
lowered = traced.lower()
t4 = time.time()
print(f"lower: {t4-t3:.1f}s hlo_bytes={len(lowered.as_text())}", flush=True)
compiled = lowered.compile()
t5 = time.time()
print(f"compile: {t5-t4:.1f}s", flush=True)
ok = bool(compiled(*args))
t6 = time.time()
print(f"run: {t6-t5:.1f}s ok={ok}", flush=True)
