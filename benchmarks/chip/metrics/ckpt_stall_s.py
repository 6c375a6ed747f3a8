"""Mean time the training loop is held by one ``AsyncCheckpointer.save``
call (the benchmark's span around it), in s."""
import statistics


def read(run):
    s = run.span_total("ckpt_save")
    return statistics.mean(s) if s else None
