"""slot_occupancy.batch: mean share of the engine's slots that decode, over
the decode steps in the window (``Step.lengths`` holds one length per
decoding slot), in %.  It falls when admissions lag behind the requests
that finish."""


def read(run):
    steps = run.steps
    if not steps:
        return None
    n_slots = run.config["engine"]["n_slots"]
    return 100.0 * sum(len(s.lengths) for s in steps) / (len(steps) * n_slots)
