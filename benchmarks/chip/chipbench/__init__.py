"""The chip benchmark's yardstick: data generation, the plain reference
round, the comparison that decides ``correct``, trace reduction, FLOP and
byte counts, and the table of peaks.  Nothing here imports the program
under test except the drivers (``drivers/``), which call its entry points.
"""
