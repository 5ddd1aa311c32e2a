"""The one error by which a run stops without a result."""


class Refused(RuntimeError):
    """The run cannot produce a result (no chip, a missing file or key)."""
