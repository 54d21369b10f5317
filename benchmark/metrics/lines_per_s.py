"""lines_per_s: all the work of the window over all its time. The
closed loop's clients send until the window closes; the lines of every
request they sent are divided by the seconds from the window's start to
the last answer, so a request still out at the close counts with the
time it takes. A request with no 200 counts as answered a minute after
the close (and fails the run)."""


def read(run):
    if not run.window:
        return None
    end = max(r["done"] if r["status"] == 200 else run.seconds + 60.0
              for r in run.window)
    return run.lines / end
