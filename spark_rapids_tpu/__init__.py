"""spark-rapids-tpu: a TPU-native accelerator for columnar SQL execution.

Built from scratch with the capabilities of NVIDIA's RAPIDS Accelerator for
Apache Spark (reference: /root/reference, spark-rapids 21.10): a physical-plan
rewrite engine that replaces supported operators/expressions with Tpu*Exec
nodes whose columnar batches are HBM-resident JAX arrays, with the kernel
library (the cuDF equivalent) implemented as XLA programs, a tiered
HBM->host->disk spill framework in place of RMM, and an ICI/DCN all-to-all
shuffle in place of the UCX RapidsShuffleManager.

Because no JVM Spark is present in this environment, the package also ships
the host engine the plugin accelerates: a Catalyst-like DataFrame/SQL layer
(`spark_rapids_tpu.sql`) whose CPU physical operators implement Spark
semantics and serve both as the bit-identical comparison baseline and as the
per-operator fallback target (the reference's contract, README.md:15-16).

Layering mirrors SURVEY.md section 1:
  L7 plugin bootstrap      spark_rapids_tpu.plugin
  L6 plan rewrite          spark_rapids_tpu.{meta,typesig,overrides,transitions,cbo}
  L5 columnar operators    spark_rapids_tpu.exec
  L4 batch/row interchange spark_rapids_tpu.exec.transitions_exec
  L3 memory/spill          spark_rapids_tpu.memory
  L2 shuffle/communication spark_rapids_tpu.shuffle
  L1 kernel library        spark_rapids_tpu.columnar  (cuDF equivalent)
  L0 device runtime        JAX / XLA
"""

__version__ = "0.1.0"

# SQL semantics require 64-bit longs/doubles; JAX defaults to 32-bit.
# Must run before any jax array is created anywhere in the package.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

from spark_rapids_tpu.conf import TpuConf  # noqa: F401,E402
