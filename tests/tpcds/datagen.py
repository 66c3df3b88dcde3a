"""Full TPC-DS data generator (all 24 tables) at miniature scale.

Role of the reference's GenTPCDSData.scala + dsdgen: deterministic star
schema covering every column of the standard TPC-DS schema
(tests/tpcds/schema.json, extracted from the public spec) with value
domains chosen so the filter literals in the 99 benchmark queries are
actually populated (d_year 1998-2002, s_state='TN',
cc_county='Williamson County', i_category/i_class/i_color/... pools).

Facts are internally consistent: returns are drawn from sales rows and
share (item_sk, ticket/order number); tickets/orders group several line
items under one customer+store+date; ext_* amounts are quantity * price.

Everything is numpy-vectorized; scale=1.0 is ~60k fact rows total and
generates in a couple of seconds.
"""

from __future__ import annotations

import datetime
import json
import os
import re
from decimal import Decimal

import numpy as np
import pyarrow as pa

_SCHEMA = json.load(open(os.path.join(os.path.dirname(__file__),
                                      "schema.json")))

EPOCH = datetime.date(1900, 1, 1)
DATE_LO = datetime.date(1997, 1, 1)
DATE_HI = datetime.date(2003, 12, 31)
SK_BASE = 2415022  # julian-style offset for date surrogate keys


def _dsk(d: datetime.date) -> int:
    return SK_BASE + (d - EPOCH).days


# value domains (public TPC-DS spec domains, filtered to what the 99
# queries reference so their literals hit real rows)
CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
              "Men", "Music", "Shoes", "Sports", "Women"]
CLASSES = ["personal", "accessories", "portable", "self-help", "classical",
           "fragrances", "pants", "computers", "shirts", "reference",
           "refernece", "stereo", "football", "birdal", "dresses",
           "maternity", "rock", "fiction", "mystery", "romance"]
COLORS = ["slate", "purple", "floral", "pale", "burlywood", "indian",
          "spring", "medium", "powder", "khaki", "brown", "honeydew",
          "deep", "light", "cornflower", "midnight", "snow", "cyan",
          "papaya", "orange", "frosted", "forest", "ghost", "chiffon",
          "blanched", "burnished", "red", "green", "blue", "white",
          "black", "yellow", "plum", "misty", "rose", "metallic"]
BRANDS = ["scholaramalgamalg #14", "amalgimporto #1", "scholaramalgamalg #7",
          "exportiunivamalg #9", "scholaramalgamalg #9", "edu packscholar #1",
          "exportiimporto #1", "importoamalg #1"] + \
    [f"brand{i} #{i % 12 + 1}" for i in range(1, 25)]
SIZES = ["medium", "extra large", "N/A", "small", "petite", "large",
         "economy"]
UNITS = ["Ounce", "Oz", "Bunch", "Ton", "N/A", "Dozen", "Box", "Pound",
         "Pallet", "Gross", "Cup", "Dram", "Each", "Tbl", "Lb", "Bundle"]
CA_STATES = ["TX", "VA", "KY", "MS", "GA", "OR", "OH", "NM", "CA", "IN",
             "WI", "LA", "CO", "IL", "WA", "NJ", "CT", "IA", "AR", "MN",
             "ND", "OK", "TN", "NY", "FL", "MI", "SD", "AL", "MO", "NE"]
CA_COUNTIES = ["Rush County", "Toole County", "Jefferson County",
               "Dona Ana County", "La Porte County", "Williamson County",
               "Orange County", "Bronx County", "Franklin Parish",
               "Walker County", "Daviess County", "Barrow County",
               "Luce County", "Richland County", "Ziebach County"]
CA_CITIES = ["Edgewood", "Fairview", "Midway", "Oakland", "Glendale",
             "Riverside", "Centerville", "Mount Zion", "Pleasant Hill",
             "Union", "Salem", "Oak Grove", "Georgetown", "Marion",
             "Greenfield", "Clinton", "Bethel", "Liberty", "Five Points",
             "Shiloh"]
STREET_TYPES = ["Street", "Ave", "Blvd", "Way", "Ct", "Dr", "Ln",
                "Parkway", "Road", "Circle"]
STREET_NAMES = ["Main", "Oak", "Park", "First", "Elm", "Maple", "Pine",
                "Cedar", "Hill", "Lake", "Sunset", "Railroad", "Church",
                "Walnut", "Spring", "Highland", "Forest", "Ridge",
                "College", "River"]
EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown"]
MARITAL = ["M", "S", "D", "W", "U"]
CREDIT = ["Low Risk", "High Risk", "Good", "Unknown"]
BUY_POTENTIAL = [">10000", "5001-10000", "1001-5000", "501-1000", "0-500",
                 "unknown"]
MEALS = ["breakfast", "lunch", "dinner", None]
SM_TYPES = ["EXPRESS", "NEXT DAY", "OVERNIGHT", "REGULAR", "TWO DAY",
            "LIBRARY"]
SM_CARRIERS = ["DHL", "BARIAN", "UPS", "FEDEX", "AIRBORNE", "USPS",
               "ZOUROS", "ZHOU", "MSC", "LATVIAN"]
FIRST_NAMES = ["James", "Mary", "John", "Linda", "Robert", "Barbara",
               "Michael", "Susan", "William", "Jessica", "David", "Sarah",
               "Richard", "Karen", "Joseph", "Nancy", "Thomas", "Lisa",
               "Charles", "Betty", "Anna", "Helen", "Sandra", "Donna",
               "Carol", "Ruth", "Sharon", "Paul", "Mark", "Donald"]
LAST_NAMES = ["Smith", "Johnson", "Williams", "Brown", "Jones", "Garcia",
              "Miller", "Davis", "Rodriguez", "Martinez", "Hernandez",
              "Lopez", "Gonzalez", "Wilson", "Anderson", "Thomas",
              "Taylor", "Moore", "Jackson", "Martin", "Lee", "Perez",
              "Thompson", "White", "Harris", "Sanchez", "Clark",
              "Ramirez", "Lewis", "Robinson"]
COUNTRIES = ["United States", "Canada", "Mexico", "Germany", "France",
             "Japan", "Brazil", "India", "Italy", "Spain", "Chile",
             "Peru", "Kenya", "Egypt", "Norway", "Greece"]
STORE_NAMES = ["ese", "ought", "able", "pri", "bar", "anti", "cally",
               "ation", "eing", "n st"]


class _Gen:
    def __init__(self, scale: float, seed: int,
                 keep: dict[str, set[str]] | None = None):
        """`keep` maps a table name to the columns worth building: the
        rest of that table's schema is left out (the Decimal conversion
        of an unread fact column costs minutes at SF1 volume). Tables not
        named in `keep` are built whole."""
        self.rng = np.random.default_rng(seed)
        self.scale = scale
        self.keep = keep or {}
        self.tables: dict[str, pa.Table] = {}

    # ---- helpers ---------------------------------------------------------
    def n(self, base: int) -> int:
        return max(1, int(base * self.scale))

    def pick(self, pool, size, null_frac=0.0):
        pool = list(pool)
        idx = self.rng.integers(0, len(pool), size)
        vals = [pool[i] for i in idx]
        if null_frac:
            mask = self.rng.random(size) < null_frac
            vals = [None if m else v for v, m in zip(vals, mask)]
        return vals

    def ints(self, lo, hi, size, null_frac=0.0, dtype=np.int32):
        v = self.rng.integers(lo, hi, size).astype(dtype)
        if null_frac:
            mask = self.rng.random(size) < null_frac
            return [None if m else int(x) for x, m in zip(v, mask)]
        return v

    def money(self, lo, hi, size):
        return np.round(self.rng.uniform(lo, hi, size), 2)

    def _finish(self, name: str, cols: dict) -> pa.Table:
        """Order + type-coerce per schema; fill any unspecified column with
        a generic value of its declared type."""
        schema = _SCHEMA[name]
        kept = self.keep.get(name)
        arrays, fields = [], []
        nrows = len(next(iter(cols.values())))
        for cname, ctype in schema:
            if kept is not None and cname not in kept:
                continue
            ctype_u = ctype.upper()
            m = re.match(r"DECIMAL\((\d+),(\d+)\)", ctype_u)
            if cname in cols:
                v = cols[cname]
            elif ctype_u == "INT" or ctype_u == "BIGINT":
                v = self.ints(1, 100, nrows, null_frac=0.05)
            elif m:
                v = self.money(1, 1000, nrows)
            elif ctype_u == "DATE":
                v = self.pick([DATE_LO + datetime.timedelta(days=i * 37)
                               for i in range(60)], nrows, null_frac=0.05)
            else:
                v = self.pick([f"{cname}_{i}" for i in range(8)], nrows,
                              null_frac=0.03)
            if m:
                p, s = int(m.group(1)), int(m.group(2))
                q = Decimal(1).scaleb(-s)
                v = pa.array([None if x is None else
                              Decimal(str(round(float(x), s))).quantize(q)
                              for x in (v.tolist() if isinstance(
                                  v, np.ndarray) else v)],
                             pa.decimal128(p, s))
            elif ctype_u in ("INT",):
                v = pa.array(v if not isinstance(v, np.ndarray)
                             else v.astype(np.int32), pa.int32())
            elif ctype_u == "BIGINT":
                v = pa.array(v if not isinstance(v, np.ndarray)
                             else v.astype(np.int64), pa.int64())
            elif ctype_u == "DATE":
                v = pa.array(v, pa.date32())
            else:
                v = pa.array([None if x is None else str(x) for x in v],
                             pa.string())
            arrays.append(v)
            fields.append(cname)
        return pa.table(dict(zip(fields, arrays)))

    # ---- dimensions ------------------------------------------------------
    def date_dim(self):
        days = (DATE_HI - DATE_LO).days + 1
        dates = [DATE_LO + datetime.timedelta(days=i) for i in range(days)]
        dow = [(d.weekday() + 1) % 7 for d in dates]  # Sunday=0 like spec
        self.tables["date_dim"] = self._finish("date_dim", {
            "d_date_sk": np.array([_dsk(d) for d in dates], np.int64),
            "d_date_id": [f"AAAAAAAA{_dsk(d):08d}" for d in dates],
            "d_date": dates,
            "d_month_seq": np.array(
                [(d.year - 1900) * 12 + d.month - 1 for d in dates]),
            "d_week_seq": np.array(
                [(d - EPOCH).days // 7 + 1 for d in dates]),
            "d_quarter_seq": np.array(
                [(d.year - 1900) * 4 + (d.month - 1) // 3 for d in dates]),
            "d_year": np.array([d.year for d in dates]),
            "d_dow": np.array(dow),
            "d_moy": np.array([d.month for d in dates]),
            "d_dom": np.array([d.day for d in dates]),
            "d_qoy": np.array([(d.month - 1) // 3 + 1 for d in dates]),
            "d_fy_year": np.array([d.year for d in dates]),
            "d_fy_quarter_seq": np.array(
                [(d.year - 1900) * 4 + (d.month - 1) // 3 for d in dates]),
            "d_fy_week_seq": np.array(
                [(d - EPOCH).days // 7 + 1 for d in dates]),
            "d_day_name": [d.strftime("%A") for d in dates],
            "d_quarter_name": [f"{d.year}Q{(d.month - 1) // 3 + 1}"
                               for d in dates],
            "d_holiday": ["Y" if (d.month, d.day) in
                          ((1, 1), (7, 4), (12, 25)) else "N"
                          for d in dates],
            "d_weekend": ["Y" if w in (0, 6) else "N" for w in dow],
            "d_following_holiday": ["N"] * days,
            "d_first_dom": np.array([_dsk(d.replace(day=1)) for d in dates],
                                    np.int64),
            "d_last_dom": np.array([_dsk(d) for d in dates], np.int64),
            "d_same_day_ly": np.array([_dsk(d) - 365 for d in dates],
                                      np.int64),
            "d_same_day_lq": np.array([_dsk(d) - 91 for d in dates],
                                      np.int64),
            "d_current_day": ["N"] * days,
            "d_current_week": ["N"] * days,
            "d_current_month": ["N"] * days,
            "d_current_quarter": ["N"] * days,
            "d_current_year": ["N"] * days,
        })

    def time_dim(self):
        n = 1440  # one row per minute; facts sample these sks
        secs = np.arange(n) * 60
        hours = secs // 3600
        self.tables["time_dim"] = self._finish("time_dim", {
            "t_time_sk": secs.astype(np.int64),
            "t_time_id": [f"TIME{s:08d}" for s in secs],
            "t_time": secs,
            "t_hour": hours,
            "t_minute": (secs // 60) % 60,
            "t_second": secs % 60,
            "t_am_pm": ["AM" if h < 12 else "PM" for h in hours],
            "t_shift": ["first" if h < 8 else "second" if h < 16 else
                        "third" for h in hours],
            "t_sub_shift": ["morning" if h < 12 else "afternoon" if h < 17
                            else "evening" if h < 21 else "night"
                            for h in hours],
            "t_meal_time": ["breakfast" if 6 <= h <= 9 else
                            "lunch" if 11 <= h <= 13 else
                            "dinner" if 17 <= h <= 20 else None
                            for h in hours],
        })

    def item(self):
        n = self.n(400)
        n_ids = max(2, int(n * 0.75))  # some item_ids span several sks
        ids = [f"AAAAAAAA{i:08d}" for i in
               self.rng.permutation(n_ids)[:n_ids]]
        item_ids = [ids[i % n_ids] for i in range(n)]
        cat_idx = self.rng.integers(0, len(CATEGORIES), n)
        price = self.money(0.5, 300, n)
        self.tables["item"] = self._finish("item", {
            "i_item_sk": np.arange(1, n + 1, dtype=np.int64),
            "i_item_id": item_ids,
            "i_rec_start_date": [datetime.date(1997, 10, 27)] * n,
            "i_rec_end_date": [None] * n,
            "i_item_desc": [f"item description {i}" for i in range(n)],
            "i_current_price": price,
            "i_wholesale_cost": np.round(price * 0.6, 2),
            "i_brand_id": self.ints(1001001, 10016017, n),
            "i_brand": self.pick(BRANDS, n),
            "i_class_id": self.ints(1, 16, n),
            "i_class": self.pick(CLASSES, n),
            "i_category_id": (cat_idx + 1).astype(np.int32),
            "i_category": [CATEGORIES[i] for i in cat_idx],
            "i_manufact_id": self.pick(
                [128, 129, 350, 677, 738, 977] + list(range(1, 1000, 7)), n),
            "i_manufact": [f"manufact{i % 100}" for i in range(n)],
            "i_size": self.pick(SIZES, n),
            "i_formulation": [f"formulation{i % 50}" for i in range(n)],
            "i_color": self.pick(COLORS, n),
            "i_units": self.pick(UNITS, n),
            "i_container": ["Unknown"] * n,
            "i_manager_id": self.pick(list(range(1, 101)), n),
            "i_product_name": [f"product{i}" for i in range(n)],
        })

    def customer_address(self):
        n = self.n(600)
        self.tables["customer_address"] = self._finish("customer_address", {
            "ca_address_sk": np.arange(1, n + 1, dtype=np.int64),
            "ca_address_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "ca_street_number": [str(self.rng.integers(1, 999))
                                 for _ in range(n)],
            "ca_street_name": self.pick(STREET_NAMES, n),
            "ca_street_type": self.pick(STREET_TYPES, n),
            "ca_suite_number": [f"Suite {i % 80}" for i in range(n)],
            "ca_city": self.pick(CA_CITIES, n),
            "ca_county": self.pick(CA_COUNTIES, n),
            "ca_state": self.pick(CA_STATES, n),
            "ca_zip": [f"{z:05d}" for z in self.ints(10000, 99999, n)],
            "ca_country": ["United States"] * n,
            "ca_gmt_offset": self.pick([-5.0, -6.0, -7.0, -8.0], n),
            "ca_location_type": self.pick(
                ["apartment", "condo", "single family"], n),
        })

    def customer_demographics(self):
        rows = []
        sk = 1
        for g in ["M", "F"]:
            for ms in MARITAL:
                for ed in EDUCATION:
                    for pe in [500, 2500, 5000, 7500, 10000]:
                        for cr in CREDIT:
                            rows.append((sk, g, ms, ed, pe, cr,
                                         sk % 7, sk % 7, sk % 7))
                            sk += 1
        a = list(zip(*rows))
        self.tables["customer_demographics"] = self._finish(
            "customer_demographics", {
                "cd_demo_sk": np.array(a[0], np.int64),
                "cd_gender": list(a[1]),
                "cd_marital_status": list(a[2]),
                "cd_education_status": list(a[3]),
                "cd_purchase_estimate": np.array(a[4]),
                "cd_credit_rating": list(a[5]),
                "cd_dep_count": np.array(a[6]),
                "cd_dep_employed_count": np.array(a[7]),
                "cd_dep_college_count": np.array(a[8]),
            })

    def household_demographics(self):
        rows = []
        sk = 1
        for ib in range(1, 21):
            for bp in BUY_POTENTIAL:
                for dep in range(0, 10, 3):
                    for veh in range(-1, 5):
                        rows.append((sk, ib, bp, dep, veh))
                        sk += 1
        a = list(zip(*rows))
        self.tables["household_demographics"] = self._finish(
            "household_demographics", {
                "hd_demo_sk": np.array(a[0], np.int64),
                "hd_income_band_sk": np.array(a[1], np.int64),
                "hd_buy_potential": list(a[2]),
                "hd_dep_count": np.array(a[3]),
                "hd_vehicle_count": np.array(a[4]),
            })

    def income_band(self):
        self.tables["income_band"] = self._finish("income_band", {
            "ib_income_band_sk": np.arange(1, 21, dtype=np.int64),
            "ib_lower_bound": np.arange(20) * 10000,
            "ib_upper_bound": (np.arange(20) + 1) * 10000,
        })

    def customer(self):
        n = self.n(1000)
        n_addr = self.tables["customer_address"].num_rows
        n_cd = self.tables["customer_demographics"].num_rows
        n_hd = self.tables["household_demographics"].num_rows
        first_dates = self.ints(_dsk(datetime.date(1998, 1, 1)),
                                _dsk(datetime.date(2001, 1, 1)), n,
                                dtype=np.int64)
        self.tables["customer"] = self._finish("customer", {
            "c_customer_sk": np.arange(1, n + 1, dtype=np.int64),
            "c_customer_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "c_current_cdemo_sk": self.ints(1, n_cd + 1, n, null_frac=0.02,
                                            dtype=np.int64),
            "c_current_hdemo_sk": self.ints(1, n_hd + 1, n, null_frac=0.02,
                                            dtype=np.int64),
            "c_current_addr_sk": self.ints(1, n_addr + 1, n,
                                           dtype=np.int64),
            "c_first_shipto_date_sk": first_dates + 30,
            "c_first_sales_date_sk": first_dates,
            "c_salutation": self.pick(["Mr.", "Mrs.", "Ms.", "Dr.",
                                       "Miss", "Sir"], n, null_frac=0.02),
            "c_first_name": self.pick(FIRST_NAMES, n, null_frac=0.02),
            "c_last_name": self.pick(LAST_NAMES, n, null_frac=0.02),
            "c_preferred_cust_flag": self.pick(["Y", "N"], n,
                                               null_frac=0.02),
            "c_birth_day": self.ints(1, 29, n, null_frac=0.02),
            "c_birth_month": self.ints(1, 13, n, null_frac=0.02),
            "c_birth_year": self.ints(1930, 1993, n, null_frac=0.02),
            "c_birth_country": self.pick(COUNTRIES, n, null_frac=0.02),
            "c_login": [None] * n,
            "c_email_address": [f"c{i}@example.com" for i in range(n)],
            "c_last_review_date": self.ints(
                _dsk(datetime.date(1999, 1, 1)),
                _dsk(datetime.date(2002, 1, 1)), n),
        })

    def store(self):
        n = max(6, self.n(12))
        emp = self.ints(200, 301, n)
        self.tables["store"] = self._finish("store", {
            "s_store_sk": np.arange(1, n + 1, dtype=np.int64),
            "s_store_id": [f"AAAAAAAA{i % max(1, n // 2):08d}"
                           for i in range(n)],
            "s_rec_start_date": [datetime.date(1997, 3, 13)] * n,
            "s_rec_end_date": [None] * n,
            "s_closed_date_sk": [None] * n,
            "s_store_name": [STORE_NAMES[i % len(STORE_NAMES)]
                             for i in range(n)],
            "s_number_employees": emp,
            "s_floor_space": self.ints(5000000, 9000000, n),
            "s_hours": self.pick(["8AM-8PM", "8AM-4PM", "8AM-12AM"], n),
            "s_manager": self.pick(FIRST_NAMES, n),
            "s_market_id": self.ints(1, 11, n),
            "s_geography_class": ["Unknown"] * n,
            "s_market_desc": [f"market desc {i}" for i in range(n)],
            "s_market_manager": self.pick(LAST_NAMES, n),
            "s_division_id": np.ones(n, np.int32),
            "s_division_name": ["Unknown"] * n,
            "s_company_id": np.ones(n, np.int32),
            "s_company_name": ["Unknown"] * n,
            "s_street_number": [str(i * 10 + 1) for i in range(n)],
            "s_street_name": self.pick(STREET_NAMES, n),
            "s_street_type": self.pick(STREET_TYPES, n),
            "s_suite_number": [f"Suite {i}" for i in range(n)],
            "s_city": [(["Fairview"] * 6 + ["Midway"] * 3 +
                        ["Salem"])[i % 10] for i in range(n)],
            "s_county": [("Williamson County" if i % 8 else
                          "Franklin Parish") for i in range(1, n + 1)],
            "s_state": ["TN"] * n,
            "s_zip": [f"{38000 + i}" for i in range(n)],
            "s_country": ["United States"] * n,
            "s_gmt_offset": [-5.0] * n,
            "s_tax_precentage": self.pick([0.00, 0.01, 0.02, 0.03], n),
        })

    def warehouse(self):
        n = max(3, self.n(5))
        self.tables["warehouse"] = self._finish("warehouse", {
            "w_warehouse_sk": np.arange(1, n + 1, dtype=np.int64),
            "w_warehouse_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "w_warehouse_name": [f"Warehouse {i}" for i in range(n)],
            "w_warehouse_sq_ft": self.ints(50000, 1000000, n),
            "w_street_number": [str(i + 1) for i in range(n)],
            "w_street_name": self.pick(STREET_NAMES, n),
            "w_street_type": self.pick(STREET_TYPES, n),
            "w_suite_number": [f"Suite {i}" for i in range(n)],
            "w_city": self.pick(CA_CITIES, n),
            "w_county": ["Williamson County"] * n,
            "w_state": ["TN"] * n,
            "w_zip": [f"{38100 + i}" for i in range(n)],
            "w_country": ["United States"] * n,
            "w_gmt_offset": [-5.0] * n,
        })

    def ship_mode(self):
        n = 20
        self.tables["ship_mode"] = self._finish("ship_mode", {
            "sm_ship_mode_sk": np.arange(1, n + 1, dtype=np.int64),
            "sm_ship_mode_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "sm_type": [SM_TYPES[i % len(SM_TYPES)] for i in range(n)],
            "sm_code": self.pick(["AIR", "SURFACE", "SEA"], n),
            "sm_carrier": [SM_CARRIERS[i % len(SM_CARRIERS)]
                           for i in range(n)],
            "sm_contract": [f"contract{i}" for i in range(n)],
        })

    def reason(self):
        n = 35
        self.tables["reason"] = self._finish("reason", {
            "r_reason_sk": np.arange(1, n + 1, dtype=np.int64),
            "r_reason_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "r_reason_desc": [f"reason {i}" for i in range(1, n + 1)],
        })

    def call_center(self):
        n = max(2, self.n(4))
        self.tables["call_center"] = self._finish("call_center", {
            "cc_call_center_sk": np.arange(1, n + 1, dtype=np.int64),
            "cc_call_center_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "cc_rec_start_date": [datetime.date(1998, 1, 1)] * n,
            "cc_rec_end_date": [None] * n,
            "cc_closed_date_sk": [None] * n,
            "cc_open_date_sk": [_dsk(datetime.date(1998, 1, 1))] * n,
            "cc_name": [f"call center {i}" for i in range(n)],
            "cc_class": self.pick(["small", "medium", "large"], n),
            "cc_employees": self.ints(100, 700, n),
            "cc_sq_ft": self.ints(10000, 50000, n),
            "cc_hours": self.pick(["8AM-8PM", "8AM-4PM"], n),
            "cc_manager": self.pick(FIRST_NAMES, n),
            "cc_mkt_id": self.ints(1, 7, n),
            "cc_mkt_class": [f"mkt class {i}" for i in range(n)],
            "cc_mkt_desc": [f"mkt desc {i}" for i in range(n)],
            "cc_market_manager": self.pick(LAST_NAMES, n),
            "cc_division": np.ones(n, np.int32),
            "cc_division_name": ["Unknown"] * n,
            "cc_company": np.ones(n, np.int32),
            "cc_company_name": ["Unknown"] * n,
            "cc_street_number": [str(i + 1) for i in range(n)],
            "cc_street_name": self.pick(STREET_NAMES, n),
            "cc_street_type": self.pick(STREET_TYPES, n),
            "cc_suite_number": [f"Suite {i}" for i in range(n)],
            "cc_city": ["Fairview"] * n,
            "cc_county": ["Williamson County"] * n,
            "cc_state": ["TN"] * n,
            "cc_zip": [f"{38200 + i}" for i in range(n)],
            "cc_country": ["United States"] * n,
            "cc_gmt_offset": [-5.0] * n,
            "cc_tax_percentage": self.pick([0.00, 0.01, 0.02], n),
        })

    def catalog_page(self):
        n = self.n(200)
        self.tables["catalog_page"] = self._finish("catalog_page", {
            "cp_catalog_page_sk": np.arange(1, n + 1, dtype=np.int64),
            "cp_catalog_page_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "cp_start_date_sk": self.ints(
                _dsk(datetime.date(1998, 1, 1)),
                _dsk(datetime.date(2002, 1, 1)), n, dtype=np.int64),
            "cp_end_date_sk": self.ints(
                _dsk(datetime.date(2002, 1, 2)),
                _dsk(datetime.date(2003, 12, 31)), n, dtype=np.int64),
            "cp_department": ["DEPARTMENT"] * n,
            "cp_catalog_number": self.ints(1, 20, n),
            "cp_catalog_page_number": self.ints(1, 100, n),
            "cp_description": [f"catalog page {i}" for i in range(n)],
            "cp_type": self.pick(["bi-annual", "quarterly", "monthly"], n),
        })

    def web_site(self):
        n = max(4, self.n(10))
        self.tables["web_site"] = self._finish("web_site", {
            "web_site_sk": np.arange(1, n + 1, dtype=np.int64),
            "web_site_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "web_rec_start_date": [datetime.date(1997, 8, 16)] * n,
            "web_rec_end_date": [None] * n,
            "web_name": [f"site_{i % max(1, n // 2)}" for i in range(n)],
            "web_open_date_sk": [_dsk(datetime.date(1998, 1, 1))] * n,
            "web_close_date_sk": [None] * n,
            "web_class": ["Unknown"] * n,
            "web_manager": self.pick(FIRST_NAMES, n),
            "web_mkt_id": self.ints(1, 7, n),
            "web_mkt_class": [f"mkt class {i}" for i in range(n)],
            "web_mkt_desc": [f"mkt desc {i}" for i in range(n)],
            "web_market_manager": self.pick(LAST_NAMES, n),
            "web_company_id": np.ones(n, np.int32),
            "web_company_name": [(["pri"] * 3 + ["able", "ese", "anti"])
                                 [i % 6] for i in range(n)],
            "web_street_number": [str(i + 1) for i in range(n)],
            "web_street_name": self.pick(STREET_NAMES, n),
            "web_street_type": self.pick(STREET_TYPES, n),
            "web_suite_number": [f"Suite {i}" for i in range(n)],
            "web_city": ["Midway"] * n,
            "web_county": ["Williamson County"] * n,
            "web_state": ["TN"] * n,
            "web_zip": [f"{38300 + i}" for i in range(n)],
            "web_country": ["United States"] * n,
            "web_gmt_offset": [-5.0] * n,
            "web_tax_percentage": self.pick([0.00, 0.01, 0.02], n),
        })

    def web_page(self):
        n = max(10, self.n(20))
        self.tables["web_page"] = self._finish("web_page", {
            "wp_web_page_sk": np.arange(1, n + 1, dtype=np.int64),
            "wp_web_page_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "wp_rec_start_date": [datetime.date(1997, 9, 3)] * n,
            "wp_rec_end_date": [None] * n,
            "wp_creation_date_sk": [_dsk(datetime.date(1998, 1, 1))] * n,
            "wp_access_date_sk": [_dsk(datetime.date(2000, 1, 1))] * n,
            "wp_autogen_flag": self.pick(["Y", "N"], n),
            "wp_customer_sk": [None] * n,
            "wp_url": ["http://www.foo.com"] * n,
            "wp_type": self.pick(["order", "general", "welcome",
                                  "protected", "feedback", "ad"], n),
            "wp_char_count": self.ints(2000, 8000, n),
            "wp_link_count": self.ints(2, 25, n),
            "wp_image_count": self.ints(1, 7, n),
            "wp_max_ad_count": self.ints(0, 4, n),
        })

    def promotion(self):
        n = max(10, self.n(30))
        self.tables["promotion"] = self._finish("promotion", {
            "p_promo_sk": np.arange(1, n + 1, dtype=np.int64),
            "p_promo_id": [f"AAAAAAAA{i:08d}" for i in range(n)],
            "p_start_date_sk": self.ints(
                _dsk(datetime.date(1998, 1, 1)),
                _dsk(datetime.date(2001, 1, 1)), n, dtype=np.int64),
            "p_end_date_sk": self.ints(
                _dsk(datetime.date(2001, 1, 2)),
                _dsk(datetime.date(2003, 6, 30)), n, dtype=np.int64),
            "p_item_sk": self.ints(
                1, self.tables["item"].num_rows + 1, n, dtype=np.int64),
            "p_cost": np.full(n, 1000.0),
            "p_response_target": np.ones(n, np.int32),
            "p_promo_name": self.pick(["ought", "able", "pri", "ese",
                                       "anti", "cally"], n),
            "p_channel_dmail": self.pick(["Y", "N"], n),
            "p_channel_email": self.pick(["N", "N", "N", "Y"], n),
            "p_channel_catalog": self.pick(["N", "N", "Y"], n),
            "p_channel_tv": self.pick(["N", "N", "N", "Y"], n),
            "p_channel_radio": self.pick(["N", "Y"], n),
            "p_channel_press": self.pick(["N", "Y"], n),
            "p_channel_event": self.pick(["N", "N", "Y"], n),
            "p_channel_demo": self.pick(["N", "Y"], n),
            "p_channel_details": [f"promo details {i}" for i in range(n)],
            "p_purpose": ["Unknown"] * n,
            "p_discount_active": self.pick(["N", "Y"], n),
        })

    # ---- facts -----------------------------------------------------------
    def _sale_dates(self, size):
        lo = _dsk(datetime.date(1998, 1, 2))
        hi = _dsk(datetime.date(2002, 12, 30))
        return self.rng.integers(lo, hi, size).astype(np.int64)

    def _null_some(self, arr, frac=0.02):
        mask = self.rng.random(len(arr)) < frac
        return [None if m else int(x) for x, m in zip(arr, mask)]

    def store_sales(self):
        n = self.n(30000)
        n_orders = max(1, n // 4)
        n_item = self.tables["item"].num_rows
        n_cust = self.tables["customer"].num_rows
        n_store = self.tables["store"].num_rows
        n_hd = self.tables["household_demographics"].num_rows
        n_cd = self.tables["customer_demographics"].num_rows
        n_addr = self.tables["customer_address"].num_rows
        n_promo = self.tables["promotion"].num_rows
        # order-level attributes shared by line items of one ticket
        o_cust = self.rng.integers(1, n_cust + 1, n_orders)
        o_store = self.rng.integers(1, n_store + 1, n_orders)
        o_date = self._sale_dates(n_orders)
        o_time = self.rng.integers(0, 1440, n_orders) * 60
        o_hd = self.rng.integers(1, n_hd + 1, n_orders)
        o_cd = self.rng.integers(1, n_cd + 1, n_orders)
        o_addr = self.rng.integers(1, n_addr + 1, n_orders)
        oi = self.rng.integers(0, n_orders, n)
        qty = self.rng.integers(1, 100, n)
        wholesale = self.money(1, 100, n)
        list_p = np.round(wholesale * self.rng.uniform(1.0, 2.0, n), 2)
        sales_p = np.round(list_p * self.rng.uniform(0.3, 1.0, n), 2)
        ext_sales = np.round(qty * sales_p, 2)
        ext_whole = np.round(qty * wholesale, 2)
        ext_list = np.round(qty * list_p, 2)
        ext_tax = np.round(ext_sales * 0.05, 2)
        coupon = np.where(self.rng.random(n) < 0.1,
                          np.round(ext_sales * 0.2, 2), 0.0)
        net_paid = np.round(ext_sales - coupon, 2)
        self._ss = dict(oi=oi, qty=qty)
        self.tables["store_sales"] = self._finish("store_sales", {
            "ss_sold_date_sk": self._null_some(o_date[oi]),
            "ss_sold_time_sk": self._null_some(o_time[oi]),
            "ss_item_sk": self.rng.integers(1, n_item + 1, n
                                            ).astype(np.int64),
            "ss_customer_sk": self._null_some(o_cust[oi]),
            "ss_cdemo_sk": self._null_some(o_cd[oi]),
            "ss_hdemo_sk": self._null_some(o_hd[oi]),
            "ss_addr_sk": self._null_some(o_addr[oi]),
            "ss_store_sk": self._null_some(o_store[oi]),
            "ss_promo_sk": self._null_some(
                self.rng.integers(1, n_promo + 1, n), 0.3),
            "ss_ticket_number": (oi + 1).astype(np.int64),
            "ss_quantity": qty.astype(np.int32),
            "ss_wholesale_cost": wholesale,
            "ss_list_price": list_p,
            "ss_sales_price": sales_p,
            "ss_ext_discount_amt": np.round(ext_list - ext_sales, 2),
            "ss_ext_sales_price": ext_sales,
            "ss_ext_wholesale_cost": ext_whole,
            "ss_ext_list_price": ext_list,
            "ss_ext_tax": ext_tax,
            "ss_coupon_amt": coupon,
            "ss_net_paid": net_paid,
            "ss_net_paid_inc_tax": np.round(net_paid + ext_tax, 2),
            "ss_net_profit": np.round(net_paid - ext_whole, 2),
        })

    def store_returns(self):
        ss = self.tables["store_sales"]
        n_ss = ss.num_rows
        take = np.sort(self.rng.permutation(n_ss)[:max(1, n_ss // 10)])
        base = ss.take(pa.array(take))
        n = base.num_rows
        sold = np.array([x if x is not None else _dsk(
            datetime.date(2000, 1, 1))
            for x in base.column("ss_sold_date_sk").to_pylist()], np.int64)
        ret_date = sold + self.rng.integers(1, 90, n)
        rqty = np.maximum(1, (np.array(
            base.column("ss_quantity").to_pylist()) *
            self.rng.uniform(0.2, 1.0, n)).astype(np.int64))
        sales_p = np.array([float(x) if x is not None else 1.0 for x in
                            base.column("ss_sales_price").to_pylist()])
        amt = np.round(rqty * sales_p, 2)
        fee = self.money(0.5, 100, n)
        self.tables["store_returns"] = self._finish("store_returns", {
            "sr_returned_date_sk": self._null_some(ret_date),
            "sr_return_time_sk": self._null_some(
                self.rng.integers(0, 1440, n) * 60),
            "sr_item_sk": np.array(base.column("ss_item_sk").to_pylist(),
                                   np.int64),
            "sr_customer_sk": self._null_some(np.array(
                [x if x is not None else 1 for x in
                 base.column("ss_customer_sk").to_pylist()], np.int64)),
            "sr_cdemo_sk": self._null_some(np.array(
                [x if x is not None else 1 for x in
                 base.column("ss_cdemo_sk").to_pylist()], np.int64)),
            "sr_hdemo_sk": self._null_some(np.array(
                [x if x is not None else 1 for x in
                 base.column("ss_hdemo_sk").to_pylist()], np.int64)),
            "sr_addr_sk": self._null_some(np.array(
                [x if x is not None else 1 for x in
                 base.column("ss_addr_sk").to_pylist()], np.int64)),
            "sr_store_sk": self._null_some(np.array(
                [x if x is not None else 1 for x in
                 base.column("ss_store_sk").to_pylist()], np.int64)),
            "sr_reason_sk": self._null_some(
                self.rng.integers(1, 36, n)),
            "sr_ticket_number": np.array(
                base.column("ss_ticket_number").to_pylist(), np.int64),
            "sr_return_quantity": rqty.astype(np.int32),
            "sr_return_amt": amt,
            "sr_return_tax": np.round(amt * 0.05, 2),
            "sr_return_amt_inc_tax": np.round(amt * 1.05, 2),
            "sr_fee": fee,
            "sr_return_ship_cost": self.money(0, 50, n),
            "sr_refunded_cash": np.round(amt * 0.7, 2),
            "sr_reversed_charge": np.round(amt * 0.2, 2),
            "sr_store_credit": np.round(amt * 0.1, 2),
            "sr_net_loss": np.round(amt * 0.5 + fee, 2),
        })

    def _channel_sales(self, prefix: str, n: int, extra: dict,
                       table: str):
        """Shared generator for catalog_sales / web_sales line items."""
        n_item = self.tables["item"].num_rows
        n_cust = self.tables["customer"].num_rows
        n_orders = max(1, n // 3)
        o_bill = self.rng.integers(1, n_cust + 1, n_orders)
        same = self.rng.random(n_orders) < 0.85
        o_ship = np.where(same, o_bill,
                          self.rng.integers(1, n_cust + 1, n_orders))
        o_date = self._sale_dates(n_orders)
        oi = self.rng.integers(0, n_orders, n)
        qty = self.rng.integers(1, 100, n)
        wholesale = self.money(1, 100, n)
        list_p = np.round(wholesale * self.rng.uniform(1.0, 2.0, n), 2)
        sales_p = np.round(list_p * self.rng.uniform(0.3, 1.0, n), 2)
        ext_sales = np.round(qty * sales_p, 2)
        ext_whole = np.round(qty * wholesale, 2)
        ext_list = np.round(qty * list_p, 2)
        ext_tax = np.round(ext_sales * 0.05, 2)
        coupon = np.where(self.rng.random(n) < 0.1,
                          np.round(ext_sales * 0.2, 2), 0.0)
        net_paid = np.round(ext_sales - coupon, 2)
        ship_cost = self.money(0.5, 40, n)
        n_cd = self.tables["customer_demographics"].num_rows
        n_hd = self.tables["household_demographics"].num_rows
        n_addr = self.tables["customer_address"].num_rows
        o_cd = self.rng.integers(1, n_cd + 1, n_orders)
        o_hd = self.rng.integers(1, n_hd + 1, n_orders)
        o_ba = self.rng.integers(1, n_addr + 1, n_orders)
        o_sa = self.rng.integers(1, n_addr + 1, n_orders)
        cols = {
            f"{prefix}_sold_date_sk": self._null_some(o_date[oi]),
            f"{prefix}_sold_time_sk": self._null_some(
                self.rng.integers(0, 1440, n) * 60),
            f"{prefix}_ship_date_sk": self._null_some(
                o_date[oi] + self.rng.integers(1, 30, n)),
            f"{prefix}_bill_customer_sk": self._null_some(o_bill[oi]),
            f"{prefix}_bill_cdemo_sk": self._null_some(o_cd[oi]),
            f"{prefix}_bill_hdemo_sk": self._null_some(o_hd[oi]),
            f"{prefix}_bill_addr_sk": self._null_some(o_ba[oi]),
            f"{prefix}_ship_customer_sk": self._null_some(o_ship[oi]),
            f"{prefix}_ship_cdemo_sk": self._null_some(o_cd[oi]),
            f"{prefix}_ship_hdemo_sk": self._null_some(o_hd[oi]),
            f"{prefix}_ship_addr_sk": self._null_some(o_sa[oi]),
            f"{prefix}_ship_mode_sk": self._null_some(
                self.rng.integers(1, 21, n)),
            f"{prefix}_warehouse_sk": self._null_some(self.rng.integers(
                1, self.tables["warehouse"].num_rows + 1, n)),
            f"{prefix}_item_sk": self.rng.integers(
                1, n_item + 1, n).astype(np.int64),
            f"{prefix}_promo_sk": self._null_some(self.rng.integers(
                1, self.tables["promotion"].num_rows + 1, n), 0.3),
            f"{prefix}_order_number": (oi + 1).astype(np.int64),
            f"{prefix}_quantity": qty.astype(np.int32),
            f"{prefix}_wholesale_cost": wholesale,
            f"{prefix}_list_price": list_p,
            f"{prefix}_sales_price": sales_p,
            f"{prefix}_ext_discount_amt": np.round(ext_list - ext_sales, 2),
            f"{prefix}_ext_sales_price": ext_sales,
            f"{prefix}_ext_wholesale_cost": ext_whole,
            f"{prefix}_ext_list_price": ext_list,
            f"{prefix}_ext_tax": ext_tax,
            f"{prefix}_coupon_amt": coupon,
            f"{prefix}_ext_ship_cost": ship_cost,
            f"{prefix}_net_paid": net_paid,
            f"{prefix}_net_paid_inc_tax": np.round(net_paid + ext_tax, 2),
            f"{prefix}_net_paid_inc_ship": np.round(
                net_paid + ship_cost, 2),
            f"{prefix}_net_paid_inc_ship_tax": np.round(
                net_paid + ship_cost + ext_tax, 2),
            f"{prefix}_net_profit": np.round(net_paid - ext_whole, 2),
        }
        cols.update(extra(oi, n) if callable(extra) else extra)
        self.tables[table] = self._finish(table, cols)

    def catalog_sales(self):
        n = self.n(15000)
        n_cc = self.tables["call_center"].num_rows
        n_cp = self.tables["catalog_page"].num_rows

        def extra(oi, n):
            return {
                "cs_call_center_sk": self._null_some(
                    self.rng.integers(1, n_cc + 1, n)),
                "cs_catalog_page_sk": self._null_some(
                    self.rng.integers(1, n_cp + 1, n)),
            }
        self._channel_sales("cs", n, extra, "catalog_sales")

    def web_sales(self):
        n = self.n(10000)
        n_wp = self.tables["web_page"].num_rows
        n_web = self.tables["web_site"].num_rows

        def extra(oi, n):
            return {
                "ws_web_page_sk": self._null_some(
                    self.rng.integers(1, n_wp + 1, n)),
                "ws_web_site_sk": self._null_some(
                    self.rng.integers(1, n_web + 1, n)),
            }
        self._channel_sales("ws", n, extra, "web_sales")

    def _returns_from(self, sales: str, sp: str, rp: str, table: str,
                      extra_cols):
        st = self.tables[sales]
        n_s = st.num_rows
        take = np.sort(self.rng.permutation(n_s)[:max(1, n_s // 10)])
        base = st.take(pa.array(take))
        n = base.num_rows

        def col(name, default=1):
            return np.array([x if x is not None else default for x in
                             base.column(name).to_pylist()], np.int64)
        sold = col(f"{sp}_sold_date_sk", _dsk(datetime.date(2000, 1, 1)))
        rqty = np.maximum(1, (np.array(
            base.column(f"{sp}_quantity").to_pylist()) *
            self.rng.uniform(0.2, 1.0, n)).astype(np.int64))
        sales_p = np.array([float(x) if x is not None else 1.0 for x in
                            base.column(f"{sp}_sales_price").to_pylist()])
        amt = np.round(rqty * sales_p, 2)
        fee = self.money(0.5, 100, n)
        cols = {
            f"{rp}_returned_date_sk": self._null_some(
                sold + self.rng.integers(1, 90, n)),
            f"{rp}_returned_time_sk": self._null_some(
                self.rng.integers(0, 1440, n) * 60),
            f"{rp}_item_sk": col(f"{sp}_item_sk"),
            f"{rp}_order_number": np.array(
                base.column(f"{sp}_order_number").to_pylist(), np.int64),
            f"{rp}_return_quantity": rqty.astype(np.int32),
            f"{rp}_return_amount" if rp == "wr" else
            f"{rp}_return_amount": amt,
            f"{rp}_return_tax": np.round(amt * 0.05, 2),
            f"{rp}_return_amt_inc_tax": np.round(amt * 1.05, 2),
            f"{rp}_fee": fee,
            f"{rp}_return_ship_cost": self.money(0, 50, n),
            f"{rp}_refunded_cash": np.round(amt * 0.7, 2),
            f"{rp}_reversed_charge": np.round(amt * 0.2, 2),
            f"{rp}_net_loss": np.round(amt * 0.5 + fee, 2),
        }
        cols.update(extra_cols(base, col, n, amt))
        self.tables[table] = self._finish(table, cols)

    def catalog_returns(self):
        def extra(base, col, n, amt):
            return {
                "cr_refunded_customer_sk": self._null_some(
                    col("cs_bill_customer_sk")),
                "cr_refunded_cdemo_sk": self._null_some(
                    col("cs_bill_cdemo_sk")),
                "cr_refunded_hdemo_sk": self._null_some(
                    col("cs_bill_hdemo_sk")),
                "cr_refunded_addr_sk": self._null_some(
                    col("cs_bill_addr_sk")),
                "cr_returning_customer_sk": self._null_some(
                    col("cs_ship_customer_sk")),
                "cr_returning_cdemo_sk": self._null_some(
                    col("cs_ship_cdemo_sk")),
                "cr_returning_hdemo_sk": self._null_some(
                    col("cs_ship_hdemo_sk")),
                "cr_returning_addr_sk": self._null_some(
                    col("cs_ship_addr_sk")),
                "cr_call_center_sk": self._null_some(
                    col("cs_call_center_sk")),
                "cr_catalog_page_sk": self._null_some(
                    col("cs_catalog_page_sk")),
                "cr_ship_mode_sk": self._null_some(
                    col("cs_ship_mode_sk")),
                "cr_warehouse_sk": self._null_some(
                    col("cs_warehouse_sk")),
                "cr_reason_sk": self._null_some(
                    self.rng.integers(1, 36, n)),
                "cr_return_amount": amt,
                "cr_store_credit": np.round(amt * 0.1, 2),
            }
        self._returns_from("catalog_sales", "cs", "cr", "catalog_returns",
                           extra)

    def web_returns(self):
        def extra(base, col, n, amt):
            return {
                "wr_refunded_customer_sk": self._null_some(
                    col("ws_bill_customer_sk")),
                "wr_refunded_cdemo_sk": self._null_some(
                    col("ws_bill_cdemo_sk")),
                "wr_refunded_hdemo_sk": self._null_some(
                    col("ws_bill_hdemo_sk")),
                "wr_refunded_addr_sk": self._null_some(
                    col("ws_bill_addr_sk")),
                "wr_returning_customer_sk": self._null_some(
                    col("ws_ship_customer_sk")),
                "wr_returning_cdemo_sk": self._null_some(
                    col("ws_ship_cdemo_sk")),
                "wr_returning_hdemo_sk": self._null_some(
                    col("ws_ship_hdemo_sk")),
                "wr_returning_addr_sk": self._null_some(
                    col("ws_ship_addr_sk")),
                "wr_web_page_sk": self._null_some(col("ws_web_page_sk")),
                "wr_reason_sk": self._null_some(
                    self.rng.integers(1, 36, n)),
                "wr_return_amt": amt,
                "wr_account_credit": np.round(amt * 0.1, 2),
            }
        self._returns_from("web_sales", "ws", "wr", "web_returns", extra)

    def inventory(self):
        n_item = self.tables["item"].num_rows
        n_wh = self.tables["warehouse"].num_rows
        # weekly snapshots over the sales window, subsampled items
        week_starts = []
        d = datetime.date(1998, 1, 2)
        while d <= datetime.date(2002, 12, 30):
            week_starts.append(_dsk(d))
            d += datetime.timedelta(days=7)
        items = np.arange(1, n_item + 1)
        sample = items[self.rng.random(n_item) <
                       min(1.0, 120 / max(1, n_item))]
        if len(sample) == 0:
            sample = items[:1]
        combos = [(w, it, wh) for w in week_starts for it in sample
                  for wh in range(1, n_wh + 1)]
        n = len(combos)
        a = list(zip(*combos))
        self.tables["inventory"] = self._finish("inventory", {
            "inv_date_sk": np.array(a[0], np.int64),
            "inv_item_sk": np.array(a[1], np.int64),
            "inv_warehouse_sk": np.array(a[2], np.int64),
            "inv_quantity_on_hand": self.ints(0, 1000, n, null_frac=0.03),
        })


def gen_tpcds_full(scale: float = 1.0, seed: int = 17
                   ) -> dict[str, pa.Table]:
    g = _Gen(scale, seed)
    g.date_dim()
    g.time_dim()
    g.item()
    g.customer_address()
    g.customer_demographics()
    g.household_demographics()
    g.income_band()
    g.customer()
    g.store()
    g.warehouse()
    g.ship_mode()
    g.reason()
    g.call_center()
    g.catalog_page()
    g.web_site()
    g.web_page()
    g.promotion()
    g.store_sales()
    g.store_returns()
    g.catalog_sales()
    g.catalog_returns()
    g.web_sales()
    g.web_returns()
    g.inventory()
    # schema conformance guard
    for name, cols in _SCHEMA.items():
        t = g.tables[name]
        assert t.column_names == [c for c, _ in cols], \
            f"{name}: {t.column_names} != {[c for c, _ in cols]}"
    return g.tables
