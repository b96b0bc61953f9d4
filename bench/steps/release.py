"""``release``: the engine forgets the subject's dataset and its plans, so the
next visit rebuilds them."""


def run(client, engine, subj, step, seed) -> dict:
    engine.release(subj.handle)
    subj.handle = None
    return {}
