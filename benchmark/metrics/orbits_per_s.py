"""End to end: every orbit the window's sound passes admitted (the pinned
count at level B for each pass from Init) over ALL the window's time on the
benchmark's own clock, from the first pass's call to the last one's return:
ramp, clocked span, overshoot and whatever lies between passes.  Nothing is
dropped: a stall anywhere in the window moves it."""


def read(ev):
    return ev["window"]["rate"]
