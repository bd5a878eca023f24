"""Process start to the first timed round: compile or cache load, the
weights, the data and the checked set-up rounds."""


def read(rec):
    return rec["setup_s"]
