"""From opening the unindexed file (engine construction, the index's
init pass) to the first answer inside phi (host clock)."""


def read(run):
    return run.first_answer_s
