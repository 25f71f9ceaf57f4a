"""XLA compilations inside the window that the persistent cache did not
serve (the target is 0: every shape is warmed up in set-up)."""


def read(rec):
    return rec["compiled_in_window"]
