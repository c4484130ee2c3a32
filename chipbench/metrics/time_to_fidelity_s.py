"""Mean seconds of the training episodes that reached the target test
fidelity in the window; the unfinished last episode is left out."""
UNIT = "s"


def read(win):
    done = win.samples.get("episode_s", [])
    return sum(done) / len(done) if done else None
