"""Test-session setup shared by every test file.

The parity tests compare the Pallas kernels, run in the interpreter on
the CPU, with the jnp references, most of them bit for bit: both sum
the same canonical K-blocks in the same order
(``repro.kernels.blocks``).  On some hosts XLA's CPU backend routes
small dots and their fusions through YNNPACK, whose summation order
depends on the shape, and the two paths then differ in the last bit.
The flag below keeps XLA's CPU dots on the path whose order the
parity contract is written against, on every host.  It must be in the
environment before the first backend is created, so it is set here, at
collection, and it is inherited by the tests' child processes.
"""
import os

_CPU_DOT_ORDER = "--xla_cpu_experimental_ynn_fusion_type="

if _CPU_DOT_ORDER not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS", ""), _CPU_DOT_ORDER) if f)
