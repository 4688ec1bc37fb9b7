"""NEXmark event generator — the benchmark's own copy.

Spec: events cycle 1 person : 3 auctions : 46 bids per 50 ordinals;
person/auction ids chain off the ordinal so every bid and auction
refers to an entity already generated; most bids/auctions go to the
most recent "hot" ids (1/hot_ratio cold); event time of ordinal i is
``base_time_ms + i * 1000 // first_event_rate``. Randomness is a pure
function of (seed, ordinal, use site), so any ordinal range can be made
in one vectorised call.

Records have the fields and the sizes of Beam's generator
(``PersonGenerator``, ``AuctionGenerator``, ``BidGenerator``): a person
averages 200 bytes, an auction 500, a bid 100, each padded to that by
its ``extra`` (desired = average - the other fields' bytes, length
desired -+ 20%). VARCHAR columns that draw from a small vocabulary
(name, city, state, item_name, channel) are indices into the lists
below; free text (``TEXT``: email_address, credit_card, description,
extra) comes as numpy object arrays of ``str``, lower-case letters
only. Whoever feeds the system maps both to its string codes. numpy
only; nothing of the program is imported here.
"""

from __future__ import annotations

import numpy as np

PERSON, AUCTION, BID = 1, 3, 46
CYCLE = PERSON + AUCTION + BID
STREAM_SHARE = {"person": PERSON / CYCLE, "auction": AUCTION / CYCLE, "bid": BID / CYCLE}
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10

CHANNELS = ["Google", "Facebook", "Baidu", "Apple"]
CITIES = ["Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland",
          "Bend", "Redmond", "Seattle", "Kent", "Cheyenne"]
STATES = ["AZ", "CA", "ID", "OR", "WA", "WY"]
_FIRST = ["Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate", "Julie",
          "Sarah", "Deiter", "Walter"]
_LAST = ["Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton", "Smith",
         "Jones", "Noris"]
NAMES = [f"{f} {l}" for f in _FIRST for l in _LAST]
ITEMS = [f"item-{c}" for c in range(997)]

# VARCHAR column -> its vocabulary (the column holds indices into it)
VOCAB = {
    ("person", "name"): NAMES,
    ("person", "city"): CITIES,
    ("person", "state"): STATES,
    ("auction", "item_name"): ITEMS,
    ("bid", "channel"): CHANNELS,
}

# free-text VARCHAR columns: object arrays of str, one value per row
TEXT = {
    ("person", "email_address"), ("person", "credit_card"),
    ("person", "extra"), ("auction", "description"), ("auction", "extra"),
    ("bid", "extra"),
}
AVG_BYTES = {"person": 200, "auction": 500, "bid": 100}
_MIN_STRING = 3  # Beam's nextString: 3 <= length < its maximum

DEFAULTS = {
    "first_event_rate": 10_000,
    "base_time_ms": 1_436_918_400_000,
    "hot_auction_ratio": 2,
    "hot_bidder_ratio": 4,
    "hot_seller_ratio": 4,
    "num_active_people": 1000,
    "num_in_flight_auctions": 100,
    "auction_duration_ms": 10_000,
}

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser."""
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & _M64
    x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _M64
    x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _M64
    return x ^ (x >> np.uint64(31))


# one odd multiplier per 8-byte word of a text
_WORD_MUL = _mix64(np.arange(1, 129, dtype=np.uint64)) | np.uint64(1)
_LETTER = (np.arange(256) % 26 + 97).astype(np.uint8)  # byte -> a..z
_TEXT_BLOCK = 1 << 12  # rows made at a time: the block stays in cache


def _last_person(eid: np.ndarray) -> np.ndarray:
    epoch, off = eid // CYCLE, eid % CYCLE
    return epoch * PERSON + np.minimum(off, PERSON - 1)


def _last_auction(eid: np.ndarray) -> np.ndarray:
    epoch, off = eid // CYCLE, eid % CYCLE
    before = off < PERSON
    epoch = np.where(before, epoch - 1, epoch)
    off = np.where(
        before | (off >= PERSON + AUCTION), AUCTION - 1, off - PERSON
    )
    return epoch * AUCTION + off


def _lens(strings) -> np.ndarray:
    return np.fromiter(map(len, strings), dtype=np.int64, count=len(strings))


class Generator:
    def __init__(self, seed: int, settings: dict | None = None):
        cfg = dict(DEFAULTS)
        unknown = set(settings or {}) - set(cfg)
        if unknown:
            raise KeyError(f"unknown generator settings {sorted(unknown)}")
        cfg.update(settings or {})
        self.cfg = cfg
        self.seed = int(seed)

    def _h(self, eid: np.ndarray, site: int) -> np.ndarray:
        seed_mix = (self.seed * 0xC2B2AE3D27D4EB4F) & 0xFFFFFFFFFFFFFFFF
        salt = (seed_mix ^ (site << 32)) & 0xFFFFFFFFFFFFFFFF
        x = eid.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        return _mix64(x ^ np.uint64(salt))

    def _below(self, eid: np.ndarray, site: int, n) -> np.ndarray:
        return (self._h(eid, site) % np.asarray(n).astype(np.uint64)).astype(
            np.int64
        )

    def _price(self, eid: np.ndarray, site: int) -> np.ndarray:
        u = (self._h(eid, site) >> np.uint64(11)) * (2.0**-53)
        return np.round(np.power(10.0, u * 6.0) * 100.0).astype(np.int64)

    def _text(self, eid: np.ndarray, site: int, lengths) -> np.ndarray:
        """One string of lower-case letters per ordinal, of the given
        lengths: a pure function of (seed, ordinal, site)."""
        lengths = np.asarray(lengths, dtype=np.int64)
        out = np.empty(len(eid), dtype=object)
        if len(eid) == 0:
            return out
        width = max(int(lengths.max()), 1)
        words = -(-width // 8)
        if words > len(_WORD_MUL):
            raise ValueError(f"text of {width} bytes is too long")
        for a in range(0, len(eid), _TEXT_BLOCK):
            x = self._h(eid[a : a + _TEXT_BLOCK], site)[:, None]
            x = x * _WORD_MUL[None, :words]
            x ^= x >> np.uint64(29)
            raw = _LETTER[x.view(np.uint8)].tobytes().decode("ascii")
            out[a : a + len(x)] = [
                raw[i : i + n]
                for i, n in zip(
                    range(0, len(raw), words * 8),
                    lengths[a : a + len(x)].tolist(),
                )
            ]
        return out

    def _between(self, eid, site, lo: int, hi: int) -> np.ndarray:
        """lo <= length < hi."""
        return lo + self._below(eid, site, hi - lo)

    def _extra(self, eid, site, stream: str, other_bytes) -> np.ndarray:
        """Beam's nextExtra: pads the record to its stream's average."""
        desired = np.maximum(AVG_BYTES[stream] - np.asarray(other_bytes), 0)
        delta = np.round(desired * 0.2).astype(np.int64)
        spread = self._below(eid, site, np.maximum(2 * delta, 1))
        return self._text(
            eid, site + 1, desired - delta + np.where(delta > 0, spread, 0)
        )

    def event_time(self, eid: np.ndarray) -> np.ndarray:
        c = self.cfg
        return c["base_time_ms"] + (eid * 1000) // c["first_event_rate"]

    def events(self, start: int, stop: int, streams) -> dict:
        """Ordinals [start, stop) of the named streams:
        {stream: {"eid": ordinals, <column>: values}}."""
        eid = np.arange(start, stop, dtype=np.int64)
        rem = eid % CYCLE
        pick = {
            "person": rem < PERSON,
            "auction": (rem >= PERSON) & (rem < PERSON + AUCTION),
            "bid": rem >= PERSON + AUCTION,
        }
        make = {
            "person": self._persons,
            "auction": self._auctions,
            "bid": self._bids,
        }
        return {s: make[s](eid[pick[s]]) for s in streams}

    def _persons(self, eid):
        name = self._below(eid, 1, len(NAMES))
        city = self._below(eid, 2, len(CITIES))
        state = self._below(eid, 3, len(STATES))
        local = self._text(eid, 20, self._between(eid, 21, _MIN_STRING, 7))
        domain = self._text(eid, 22, self._between(eid, 23, _MIN_STRING, 5))
        email = np.empty(len(eid), dtype=object)
        email[:] = [f"{a}@{b}.com" for a, b in zip(local, domain)]
        digits = f"{{:016d}}".format
        card = np.empty(len(eid), dtype=object)
        card[:] = [
            f"{d[:4]} {d[4:8]} {d[8:12]} {d[12:]}"
            for d in map(digits, self._below(eid, 24, 10**16).tolist())
        ]
        other = (
            8 + _lens(NAMES)[name] + _lens(email) + 19
            + _lens(CITIES)[city] + _lens(STATES)[state]
        )
        return {
            "eid": eid,
            "id": _last_person(eid) + FIRST_PERSON_ID,
            "name": name,
            "email_address": email,
            "credit_card": card,
            "city": city,
            "state": state,
            "date_time": self.event_time(eid),
            "extra": self._extra(eid, 25, "person", other),
        }

    def _auctions(self, eid):
        c = self.cfg
        aid = _last_auction(eid) + FIRST_AUCTION_ID
        last_p = _last_person(eid)
        hot = self._below(eid, 4, c["hot_seller_ratio"]) > 0
        hot_seller = (last_p // c["hot_seller_ratio"]) * c["hot_seller_ratio"]
        active = np.maximum(np.minimum(last_p + 1, c["num_active_people"]), 1)
        cold_seller = last_p - self._below(eid, 5, active)
        initial = self._price(eid, 6)
        ts = self.event_time(eid)
        item = aid % len(ITEMS)
        description = self._text(
            eid, 27, self._between(eid, 28, _MIN_STRING, 100)
        )
        other = 8 + _lens(ITEMS)[item] + _lens(description) + 5 * 8
        return {
            "eid": eid,
            "id": aid,
            "item_name": item,
            "description": description,
            "initial_bid": initial,
            "reserve": initial + self._price(eid, 7) // 10,
            "date_time": ts,
            "expires": ts + c["auction_duration_ms"],
            "seller": np.where(hot, hot_seller, cold_seller) + FIRST_PERSON_ID,
            "category": FIRST_CATEGORY_ID + self._below(eid, 8, 5),
            "extra": self._extra(eid, 29, "auction", other),
        }

    def _bids(self, eid):
        c = self.cfg
        last_a = _last_auction(eid)
        hot_a = self._below(eid, 9, c["hot_auction_ratio"]) > 0
        hot_auction = (last_a // c["hot_auction_ratio"]) * c["hot_auction_ratio"]
        in_flight = np.maximum(
            np.minimum(last_a + 1, c["num_in_flight_auctions"]), 1
        )
        cold_auction = last_a - self._below(eid, 10, in_flight)
        last_p = _last_person(eid)
        hot_b = self._below(eid, 11, c["hot_bidder_ratio"]) > 0
        hot_bidder = (
            last_p // c["hot_bidder_ratio"]
        ) * c["hot_bidder_ratio"] + 1
        active = np.maximum(np.minimum(last_p + 1, c["num_active_people"]), 1)
        cold_bidder = last_p - self._below(eid, 12, active)
        channel = self._below(eid, 14, len(CHANNELS))
        return {
            "eid": eid,
            "auction": np.where(hot_a, hot_auction, cold_auction)
            + FIRST_AUCTION_ID,
            "bidder": np.where(hot_b, hot_bidder, cold_bidder)
            + FIRST_PERSON_ID,
            "price": self._price(eid, 13),
            "channel": channel,
            "date_time": self.event_time(eid),
            "extra": self._extra(
                eid, 31, "bid", 4 * 8 + _lens(CHANNELS)[channel]
            ),
        }
