"""Chip benchmark of the multi-model server: cells, traffic, metrics, references."""
