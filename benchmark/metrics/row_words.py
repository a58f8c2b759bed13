"""32-bit words of the packed row the traced window's segments streamed
(``row_words`` among the ``args`` of the program's ``segment`` spans:
``schema.P``): what every candidate costs through pack, stream, d2h, the host
store and the upload (benchmark/harness/histred.py).  Nothing to read where
the spans do not say it: a program older than the count."""

from benchmark.harness import histred


def read(ev):
    red = histred.of(ev)
    return red and red["row_words"]
