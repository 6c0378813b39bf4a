"""Token events that reached clients inside the window over the window's seconds."""
from benchmark.harness.readers import serve_tok_s as read  # noqa: F401
