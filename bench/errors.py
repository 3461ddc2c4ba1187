"""The one error a run reports instead of a result.

Its own module: ``bench/run.py`` runs as ``__main__``, so a class defined
there is not the one that ``bench/system.py`` or ``bench/sizes.py`` could
import and raise.
"""


class BenchError(RuntimeError):
    """The run cannot produce a result."""
