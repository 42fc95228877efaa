"""apex_tpu.testing — test/bench harness (ref: apex/transformer/testing)."""
