"""Peacock reproduction (arXiv:1405.4402) on a jax/Pallas TPU mapping."""
