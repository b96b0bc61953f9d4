"""``plan_build`` stage time per subject visit (ms): the Gram and the factorisations."""


def read(rec):
    st, visits = rec.get("stages"), rec.get("visits")
    if not st or not visits or not st["plan_build"]["count"]:
        return None
    return 1e3 * st["plan_build"]["sum_s"] / visits
