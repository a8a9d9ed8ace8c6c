# Pallas TPU kernels for the compute hot spots. Each subpackage:
#   kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
#   ops.py    — jit'd public wrapper (pytree handling, padding, dispatch)
#   ref.py    — pure-jnp oracle used by the allclose test sweeps
#
# On a TPU backend the compiled Pallas body runs (`chip_smoke.py` drives
# svrg_update on a v5e; tests/test_tpu_compile.py compiles for a described
# v5e topology). The CPU test suite runs the jnp references, or the bodies
# in interpret mode via REPRO_KERNEL_MODE=interpret (kernels/dispatch.py).
