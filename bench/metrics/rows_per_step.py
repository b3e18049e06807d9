"""Rows of a decode step that carried a request still wanting its token,
averaged over the window's decode steps (harness counts from each lane's
wave). Padding rows and rows whose request is done do not count."""


def read(ctx):
    win = ctx["window"]
    if not win.decode_steps:
        return None
    return win.useful_rows / win.decode_steps
