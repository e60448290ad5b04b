"""Plain float32 references, one per configuration: ``jax.numpy`` only,
no kernel, no cache, no batching tricks, full matmul precision. They
decide ``correct``; nothing under ``flexflow_tpu/`` is imported here."""
