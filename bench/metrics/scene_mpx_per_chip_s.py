"""Scene megapixels committed by the job, over the time from the window's
start to its last commit, per chip of the cell."""


def read(run):
    if "scene_px" not in run:
        return None
    return run["scene_px"] / 1e6 / run["span_s"] / run["chips"]
