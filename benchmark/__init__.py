"""The chip benchmark of nnstreamer-tpu: see ``benchmark/README.md``."""


class BenchmarkError(RuntimeError):
    """The run cannot produce a result line."""
