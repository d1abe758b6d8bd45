"""The benchmark of paddle_tpu: harness, yardstick, data. See README.md."""
