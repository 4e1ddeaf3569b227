"""Process start to the start of the window, compilation included."""


def read(record):
    return record["setup_s"]
