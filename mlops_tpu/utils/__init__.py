"""Shared utilities: structured logging, jsonl metrics, timing."""

from mlops_tpu.utils.jsonl import JsonlWriter

__all__ = ["JsonlWriter"]
