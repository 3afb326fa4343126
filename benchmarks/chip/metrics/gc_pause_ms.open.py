"""Time the garbage collector held the process inside the window, in ms:
the collections the server's collector watch recorded
(``repro.obs.hostpause``), clipped to the window.  Nothing when the
program has no watch or recorded no collection."""


def read(run):
    try:
        from repro.obs import hostpause
    except ImportError:
        return None
    w = run.window
    got = hostpause.pauses(w.t0, w.t_end or w.t0 + w.seconds)
    return 1000.0 * sum(d for _, d, _ in got) if got else None
