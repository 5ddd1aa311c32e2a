"""Share of live requests' step decisions that accepted the draft step
(GSI selection, ``_draft_phase`` / ``core/gsi.py``): the program's
``accepted`` and ``decisions`` counters over the window."""


def read(ctx):
    d = ctx.counters["decisions"]
    return 100.0 * ctx.counters["accepted"] / d if d else None
