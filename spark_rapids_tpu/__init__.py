"""spark_rapids_tpu — TPU-native columnar SQL accelerator.

A from-scratch, TPU-first re-design of the RAPIDS Accelerator for Apache
Spark (reference: JustPlay/spark-rapids).  Where the reference pairs a JVM
plan-rewrite plugin with cuDF/CUDA kernels over JNI, this framework pairs a
Python plan-rewrite engine with XLA/Pallas kernels over JAX, device columns
are XLA buffers instead of cuDF columns, and shuffle repartitions columnar
batches over ICI via ``lax.all_to_all`` instead of UCX point-to-point.

Layer map (mirrors SURVEY.md §1):

* ``plan/``     — L5: overrides/rewrite engine, type checking, transitions
* ``exec/``     — L4: columnar physical operators (TPU + CPU-fallback)
* ``ops/``      — L4: expression library lowered to jax/XLA
* ``io/``       — L4: Parquet/CSV/JSON scan + write framing
* ``shuffle/``  — L3: partitioning, serialization, shuffle managers (host + ICI)
* ``runtime/``  — L2: device manager, semaphore, spill, OOM-retry
* ``columnar/`` — L2: column/batch data model (static-shape, bucketed)
* ``parallel/`` — mesh/collective layer (ICI/DCN)
* ``sql/``      — L7: DataFrame/SQL user API (the measured pipelines are
  ``benchmark/queries/`` — TPC-H through the public API)

Reference parity citations use the form ``[REF: <upstream path> :: <Symbol>]``
per SURVEY.md (the reference mount was empty; citations are upstream search
keys).
"""

__version__ = "0.1.0"

from spark_rapids_tpu.conf import RapidsConf  # noqa: F401
from spark_rapids_tpu.runtime.device import ensure_initialized  # noqa: F401
