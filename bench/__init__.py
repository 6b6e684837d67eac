"""On-chip benchmark of the streaming Bayesian-RNN server (see PERF.md)."""
