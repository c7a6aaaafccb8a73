"""Set-up seconds: process start to the first timed request or step."""


def read(run):
    return run["result"]["setup_s"]
