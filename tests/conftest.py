"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

This is the "multi-node without a cluster" tier (SURVEY.md §4): the
reference's analog is Spark local[*] mode (`shared/base.template:27`); ours
is XLA's host-platform device multiplexing, so every sharding/collective
path is exercised without TPU hardware.

Must run before any jax import — pytest imports conftest first.
"""

import os

# force, don't setdefault: tests run on the local virtual CPU mesh
# whatever accelerator the ambient environment names, and every child
# process a test starts inherits the pin
os.environ["JAX_PLATFORMS"] = "cpu"

# plan verification is ALWAYS on under tests (nds_tpu/analysis): every
# plan any test produces gets its structural invariants checked at
# planning time and again post-staging on the device path
os.environ["NDS_TPU_VERIFY_PLANS"] = "1"

# artifact digest verification likewise (nds_tpu/io/integrity.py):
# every warehouse/cache read a test performs re-hashes against its
# table manifest; files without a manifest load unverified, so
# fixtures predating manifests keep working
os.environ["NDS_TPU_VERIFY_DIGESTS"] = "1"

# runtime lock-order sanitizer (nds_tpu/analysis/locksan.py): every
# engine lock created in the test process (and in the fleet/soak/serve
# subprocesses, which inherit the env) is wrapped to record per-thread
# acquisition order — an inversion any test provokes prints loudly and
# fails the static_checks locksan gate. setdefault so NDS_TPU_LOCKSAN=0
# can opt a debugging session out.
os.environ.setdefault("NDS_TPU_LOCKSAN", "1")


flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "collective_call_terminate" not in flags:
    # virtual devices are threads sharing the host's cores: on a small
    # box the 8 per-device threads serialize, and a heavy pre-collective
    # section can overrun XLA CPU's default 40 s rendezvous termination
    # (observed on q72's exchange at 1 core: "only 2 of them arrived")
    flags += (" --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
              " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
os.environ["XLA_FLAGS"] = flags
os.environ.setdefault("JAX_ENABLE_X64", "true")
