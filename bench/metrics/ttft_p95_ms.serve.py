"""95th percentile over every request due in the serving window of its
first token - due time, open loop (the driver's own record; a request
with no first token by the window's end enters as end - due).  The tail
beside the end-to-end median: with about 60 requests in a window it
rests on three samples and swings by a fifth between runs of one seed."""
import numpy as np


def read(rec):
    ttft = rec.requests.get("ttft_s")
    if not ttft:
        return None
    return 1e3 * float(np.percentile(np.asarray(ttft, np.float64), 95))
