"""Whole LoRA rounds' share of the chips' peak: ``round_mfu``'s reading of
the granite cell, whose driver counts each round's required FLOPs and
bytes in ``bench/work_lora.py`` (the live honest clients' training tokens
and the held-out forward, from the layer shapes)."""

from bench.metrics.round_mfu import read  # noqa: F401
