"""Per-layer metric readers, one module per metric stem. Each has
`read(ctx) -> float | None`: None where its cell gives it nothing to
read, and the harness then leaves the metric out. `ctx` holds the
reduced trace ("trace", see bench/trace.py, with "host_steps": the
harness's records of the traced steps), the measured window's step
records ("window_steps", "open", "close"), the configuration's sizes
("model"), the traffic file ("traffic"), the chip's peaks ("peaks") and
the part of the metric's name after the '.' ("variant")."""
