"""Pallas TPU kernels for the paper's compute hot-spots.

  uct_select.py      — Tree-Parallel Selection + virtual loss (paper §IV)
  uct_backup.py      — BackUp from memoized paths (paper §IV-E)
  flash_attention.py — LM simulation-backend prefill attention
  ops.py             — jit wrappers matching repro.core.intree's API
  ref.py             — pure-jnp oracles (transitively bit-exact vs the
                       sequential CPU program)

Kernels are compiled by Mosaic for TPU and run in the Pallas interpreter
on the CPU backend; ops.py picks by the platform a program is lowered
for.
"""
