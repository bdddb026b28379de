"""Two jobs and no clock: what a test drives.

    "loop": "two_jobs"
"""


def drive(one_job, seconds, mix):
    one_job(0)
    one_job(1)
    return 2
