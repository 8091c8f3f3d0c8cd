"""The on-chip GNN training benchmark: cells, data, loop, reference,
trace reduction and the arithmetic of the yardstick."""
