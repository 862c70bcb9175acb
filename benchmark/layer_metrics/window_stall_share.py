"""Host loop: the share of the untraced stretch, in percent, that its steps
took beyond a median step each. The end-to-end rate is one step per median
step time and so does not see a stall; this does. On a device-bound cell the
queued step hides the host's stalls and it is near 0."""


def read(run):
    if len(run.window.stamps) < 3:
        return None
    return 100.0 * run.window.stall_share()
