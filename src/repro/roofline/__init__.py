from .analysis import (HW, collective_bytes_from_hlo,
                       roofline_report, parse_hlo_collectives)
