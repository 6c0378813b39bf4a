"""Record the small traces under ``tests/benchmark/data``: run a cell with
``--trace 1`` on the chip and keep the first quarter second of its traced
window as JSON, in the form ``benchmark.harness.trace.reduce`` takes.

    python3 tests/benchmark/record_trace_head.py <out.json> <benchmark.run arguments>
"""
import json
import sys


def head(loaded: dict, seconds: float = 0.25) -> dict:
    from benchmark.harness.trace import WINDOW
    win = [e for e in loaded["host"] if e[0] == WINDOW][0]
    end = win[1] + int(seconds * 1e9)
    inside = lambda e: win[1] <= e[1] and e[1] + e[2] <= end  # noqa: E731
    return {"devices": {str(d): {k: [e for e in v if inside(e)]
                                 for k, v in lines.items()}
                        for d, lines in loaded["devices"].items()},
            "host": [[WINDOW, win[1], end - win[1]]] + [
                e for e in loaded["host"] if e[0] != WINDOW and inside(e)]}


if __name__ == "__main__":
    from benchmark.harness import trace
    from benchmark.run import main
    out, load = sys.argv[1], trace.load

    def load_and_keep(path):
        loaded = load(path)
        with open(out, "w") as f:
            json.dump(head(loaded), f)
        return loaded

    trace.load = load_and_keep
    sys.exit(main(sys.argv[2:]))
