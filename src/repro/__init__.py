"""repro — distributed variational sparse-GP/GPLVM inference (NIPS 2014)
plus the multi-arch LM substrate and TPU launch/roofline tooling.

GP inference follows the paper in float64 (collapsed-bound Cholesky math is
ill-conditioned in f32); x64 is enabled globally and the LM substrate passes
explicit f32/bf16 dtypes everywhere.

Programs are partitioned with GSPMD, not Shardy: with Shardy, the TPU
compiler (libtpu 0.0.34) refuses an f64 Cholesky in any program over more
than one device — the replicated global step of the bound, the predictive
state — with "A tuple parameter that is being flattened shouldn't have
frontend attributes".  tests/test_tpu_compile.py compiles that case for a
four-chip v5e, so a JAX or libtpu upgrade shows when the switch can go.
"""
import jax

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_use_shardy_partitioner", False)

__version__ = "1.0.0"
