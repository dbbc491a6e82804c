"""Pallas TPU kernels for the framework's hot primitives (opt-in;
the XLA formulations remain the defaults — see ops.labels)."""

from rocalphago_tpu.ops.labels import pallas_labels

__all__ = ["pallas_labels"]
