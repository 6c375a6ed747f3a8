"""Mean time of one ``launch.serve.generate`` call in a cold start: from the
end of the restore to the request's answer on the host (programs built
from the compile cache, prefill, decode), in s."""
import statistics


def read(run):
    s = run.span_total("generate")
    return statistics.mean(s) if s else None
