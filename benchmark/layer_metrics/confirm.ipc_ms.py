"""Mean of `ipt_stage_us{stage="confirm_ipc"}` over the window: what the
hop to the walker processes costs a dispatch, summed over its shares —
each share's send on the dispatch thread to its answer in its waiter's
hands, less the walker's own walk time (the pipe both ways, the walker's
unpickling and pickling, the wake-ups); 0 for a dispatch walked inline.
Beside it `stage="confirm_walk"` adds up the shares' time on their waiter
threads.  Nothing to read from a program without the span.  Layer:
confirm."""


def read(ctx):
    return ctx["window"].stage_mean_ms("confirm_ipc")
