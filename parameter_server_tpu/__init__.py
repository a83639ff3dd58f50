"""parameter_server_tpu — a TPU-native parameter-server framework.

A from-scratch re-expression of the OSDI'14-generation C++ parameter server
(reference: ziyue1987/parameter_server — scheduler/server/worker processes
over ZeroMQ with Push/Pull on a range-sharded sparse key->value model) as an
idiomatic JAX/XLA framework for TPU pods:

- "Servers" are HBM-resident parameter+optimizer slices, range-sharded over a
  ``jax.sharding.Mesh`` axis (GSPMD), not processes (ref: src/system/,
  src/parameter/ in the reference tree).
- ``Push``/``Pull`` lower to XLA collectives (reduce-scatter / all-gather or
  masked-gather + psum) under ``shard_map`` on ICI, not ZeroMQ point-to-point
  (ref: src/system/van.*, src/parameter/shared_parameter.h).
- Server-side updaters (SGD / AdaGrad / FTRL-proximal) are elementwise row
  math that XLA fuses into the push's scatter over the sharded state (ref:
  src/app/linear_method/async_sgd.h server entries).
- The SSP bounded-delay clock survives as a host-side gate on step dispatch
  (ref: src/system/executor.* wait_time dependency tracking).

Package layout:
    utils/      config, hashing, key ranges, metrics, logging   (ref src/util/)
    kv/         the sharded KV store: pull/push/updaters        (ref src/parameter/)
    ops/        device kernels: segment ops, CSR matvec         (ref hot loops)
    parallel/   mesh construction, SSP clock, workload pool     (ref src/system/)
    data/       parsers, localizer, minibatch readers           (ref src/data/)
    models/     apps: linear_method, MF, word2vec, wide&deep    (ref src/app/)
    filters/    bandwidth codecs for DCN paths                  (ref src/filter/)
"""

__version__ = "0.1.0"

from parameter_server_tpu.utils.keyrange import KeyRange  # noqa: F401
