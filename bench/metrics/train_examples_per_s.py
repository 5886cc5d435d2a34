"""Examples of the training steps run in the window, over the window: from
the first step's dispatch to the last step's loss on the host."""


def read(run):
    if run.train is None:
        return None
    return run.train["examples"] / run.window_s
