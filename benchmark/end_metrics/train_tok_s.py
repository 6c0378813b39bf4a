"""Tokens of every optimizer step completed in the window over first call to last block_until_ready."""
from benchmark.harness.readers import train_tok_s as read  # noqa: F401
