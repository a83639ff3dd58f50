"""Device kernels: CSR segment ops and losses."""

from parameter_server_tpu.ops.sparse import (  # noqa: F401
    csr_grad,
    csr_logits,
    logistic_loss,
)
