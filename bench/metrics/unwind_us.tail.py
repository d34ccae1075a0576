"""Mean harness-clock time a path request spent on the client after its
ticket was done, unwinding its polylines from the host index
(``openloop``'s waiter), over every path request answered in the window;
None where the mix asks for no routes.

It is wall time on the waiter thread, so it includes the time the walk
waits for the interpreter lock behind admission and the serve loop: a
change that only shortens those waits moves it as much as a faster
walk."""


def read(m):
    if not len(m.unwind_s):
        return None
    return 1e6 * float(m.unwind_s.mean())
