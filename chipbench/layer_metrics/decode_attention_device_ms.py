"""Device time of one execution of the decode program in operations whose
name stack holds the program's scope ``decode_attention``
(``ops/decode_attention.py``: the page gather, the scores, the online
softmax), in milliseconds. Prints the page gather's part of it."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.decode_split_ms(cell)
    if split is None:
        return None
    print(f"chipbench: decode_attention_device_ms: page_gather "
          f"{split['page_gather']:.3f} ms of {split['attention']:.3f} ms, "
          f"the program {split['total']:.3f} ms", flush=True)
    return split["attention"]
