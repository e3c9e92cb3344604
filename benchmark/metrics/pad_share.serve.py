"""Token positions dispatched that were padding, of all dispatched
(`engine.stats`: counts, exact)."""


def read(run):
    d = run.measured.get("tokens_dispatched")
    if not d:
        return None
    return 100.0 * run.measured["tokens_padded"] / d
