"""plan_build_s: host clock around ``compress_ffn`` (plan + pack) in set-up."""


def read(rec):
    return rec.get("plan_build_s")
