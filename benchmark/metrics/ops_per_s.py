"""ops_per_s (`.crashed`, `.plain`, `.keyed`): client operations
(invocations) of the histories whose check ended in the window, over the
window's seconds."""


def read(run):
    return run.ops / run.window_s
