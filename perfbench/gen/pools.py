"""Value domains of the TPC-DS dimensions that the cells' queries read
(the specification's domains; the repo's own generator,
tests/tpcds/datagen.py, holds the same lists)."""

CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
              "Men", "Music", "Shoes", "Sports", "Women"]
CLASSES = ["personal", "accessories", "portable", "self-help", "classical",
           "fragrances", "pants", "computers", "shirts", "reference",
           "refernece", "stereo", "football", "birdal", "dresses",
           "maternity", "rock", "fiction", "mystery", "romance"]
# the syllables dsdgen builds brand names from ("amalgimporto #1",
# "edu packscholar #1", "exportiunivamalg #9")
BRAND_SYLLABLES = ["amalg", "importo", "edu pack", "exporti", "scholar",
                   "corp", "brand", "univ", "nameless", "maxi"]
EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown"]
MARITAL = ["M", "S", "D", "W", "U"]
CREDIT = ["Low Risk", "High Risk", "Good", "Unknown"]
