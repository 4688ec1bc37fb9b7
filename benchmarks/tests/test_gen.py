"""The generator: records have Beam's fields and average sizes, and any
ordinal range gives the same events as the whole."""

import numpy as np

import nexmark_gen as g

SETTINGS = {"first_event_rate": 159_000}


def _bytes(stream, cols):
    total = np.zeros(len(cols["eid"]))
    for name, v in cols.items():
        if name == "eid":
            continue
        if (stream, name) in g.TEXT:
            total += g._lens(v)
        elif (stream, name) in g.VOCAB:
            total += g._lens(g.VOCAB[(stream, name)])[v]
        else:
            total += 8
    return total


def test_records_average_the_sources_sizes():
    ev = g.Generator(2**31 + 17, SETTINGS).events(
        0, 200_000, ["person", "auction", "bid"])
    assert list(ev["person"]) == [
        "eid", "id", "name", "email_address", "credit_card", "city", "state",
        "date_time", "extra"]
    assert list(ev["auction"]) == [
        "eid", "id", "item_name", "description", "initial_bid", "reserve",
        "date_time", "expires", "seller", "category", "extra"]
    for stream, cols in ev.items():
        mean = _bytes(stream, cols).mean()
        # (Beam leaves one 8-byte field of a person and of an auction out
        # of the sum it pads)
        assert abs(mean - g.AVG_BYTES[stream]) < 0.05 * g.AVG_BYTES[stream]
    p = ev["person"]
    assert all(len(c) == 19 and c[4] == " " for c in p["credit_card"][:50])
    assert all(e.endswith(".com") and "@" in e for e in p["email_address"][:50])
    extra = ev["auction"]["extra"]
    assert len(set(extra.tolist())) == len(extra)  # free text, not a vocabulary
    assert all(x.isalpha() and x.islower() for x in extra[:50])


def test_a_range_gives_the_events_of_the_whole():
    gen = g.Generator(7, SETTINGS)
    whole = gen.events(0, 30_000, ["person", "auction"])
    part = gen.events(10_000, 20_000, ["person", "auction"])
    for stream, cols in part.items():
        i = np.searchsorted(whole[stream]["eid"], cols["eid"][0])
        for name, v in cols.items():
            assert (whole[stream][name][i : i + len(v)] == v).all()
    other = g.Generator(8, SETTINGS).events(0, 1000, ["person"])["person"]
    assert (other["extra"] != whole["person"]["extra"][:len(other["eid"])]).all()
