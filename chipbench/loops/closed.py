"""The closed loop of `clients` clients: a whole job, then the next, until
`seconds` have passed; a job that has started when the time runs out is
finished and counted. One client is one script, as `resource/knn.sh` is.

    "loop": "closed", "clients": 1
"""

import time


def drive(one_job, seconds, mix):
    """Call `one_job(i)` for i = 0, 1, ... on this thread for `seconds`
    seconds; returns the number of jobs driven."""
    if int(mix["clients"]) != 1:
        raise ValueError("the closed loop drives one client: the program's "
                         "job entry holds the chip for a whole job")
    t0 = time.perf_counter()
    i = 0
    while True:
        one_job(i)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            return i
