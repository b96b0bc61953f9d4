"""``register``: the subject's dataset enters the engine (features, folds, λ)."""


def run(client, engine, subj, step, seed) -> dict:
    subj.handle = client.register(subj.x, (subj.te, subj.tr), subj.lam)
    return {}
