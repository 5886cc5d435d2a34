"""Pallas TPU kernels for the paper's compute hot-spots (interpret mode on
the CPU backend, compiled everywhere else — see ``ops.interpret_mode``)."""

from .ops import dlrm_interact, interpret_mode, qr_lookup, serve_bag_pool

__all__ = ["dlrm_interact", "interpret_mode", "qr_lookup", "serve_bag_pool"]
