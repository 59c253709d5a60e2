"""Reproducible experiment scripts: the robust-aggregation ablation
(`robust_ablation.py`) through `run_federated`."""
