"""Engine construction over the unindexed file: ``TileIndex``'s init
pass (host clock, in the traced run)."""


def read(run):
    return run.init_s
