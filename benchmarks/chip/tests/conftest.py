import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "..", "..", "src"))
