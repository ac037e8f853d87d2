"""The yardstick's own code: cell resolution, process discipline, window and
metric arithmetic, the peaks table, kernel cost functions, the trace
reduction and the per-layer readers. Nothing here imports the program; the
drivers under ``benchmark/drivers`` do that, inside a child process."""
