"""Host seconds of loading the data: the seed's tables made on the device,
wrapped as the program's tables, and the catalog's statistics of them."""


def read(rec):
    return rec["build_s"]
