"""Refinement rounds per view: the mean of the answers' ``batch_rounds``."""


def read(run):
    rounds = [r.batch_rounds for r in run.results if r is not None]
    return sum(rounds) / len(rounds) if rounds else None
